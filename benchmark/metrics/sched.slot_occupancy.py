"""Share of the window's slot-steps that delivered a token: the counter's difference at each delivery in the window
over ``decode_chunk`` x slots (a slot that is empty, or whose request ends inside a chunk, delivers none for the rest)."""


def read(obs):
    s = obs["occupancy"][obs["i_open"] + 1: obs["i_close"] + 1]
    return 100.0 * sum(s) / len(s) if s else None
