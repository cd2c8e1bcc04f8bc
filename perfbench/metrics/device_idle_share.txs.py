"""See perfbench/layers.py: idle_share, in the full node's cells."""

from perfbench.layers import idle_share as read  # noqa: F401
