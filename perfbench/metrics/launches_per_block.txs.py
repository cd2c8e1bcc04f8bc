"""See perfbench/layers.py: launches_per_block, in the full node's cells."""

from perfbench.layers import launches_per_block as read  # noqa: F401
