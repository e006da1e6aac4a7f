"""Bytes one cached token takes over every pool of every layer, as the engine sized its pools from the model's cache
spec (``kv_stats()["bytes_per_token"]``): 9,216 for latent rows of 576 in 8 attention blocks, 327,680 if keys and values
were cached expanded per head. Nothing to read on a program whose ``kv_stats`` has no such key."""


def read(obs):
    return (obs.get("kv_after") or {}).get("bytes_per_token")
