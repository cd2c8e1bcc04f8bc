"""See perfbench/layers.py: kernel_roofline, in the full node's cells."""

from perfbench.layers import kernel_roofline as read  # noqa: F401
