"""Longest time between two consecutive loss scalars in the traced run's window: one step in a quiet run, a stall where
it is several. The run's stderr has its offset and what the host did meanwhile."""

from benchmark import window


def read(obs):
    return window.longest_gap_ms(obs["gaps"])
