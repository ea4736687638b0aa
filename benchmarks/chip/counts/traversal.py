"""Work of an exact VAT traversal of n points of width d.

The problem's work, whatever the engine: every one of the n(n-1)/2 pair
distances once, at 2d flops each (d products, d sums), and one read of
the float32 points.
"""


def work(n: int, d: int):
    """(flops, bytes) of one traversal."""
    return n * (n - 1) / 2 * 2 * d, n * d * 4
