"""See perfbench/layers.py: node_ms_per_block, in the full node's cells."""

from perfbench.layers import node_ms_per_block as read  # noqa: F401
