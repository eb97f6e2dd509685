"""A small counter-based generator for everything the decks draw.

Decks, pairings, request order and arrival jitter must be the same bits on
every machine and every library version, so they do not come from numpy or
``random``: this is SplitMix64, eleven lines, with the three draws the
benchmark needs.  Token ids (bulk, value-irrelevant) come from numpy.
"""
from __future__ import annotations

_MASK = (1 << 64) - 1


class SplitMix:
    def __init__(self, seed: int, stream: int = 0):
        # the stream separates independent uses of one --seed
        self._s = (int(seed) * 0x9E3779B97F4A7C15
                   + int(stream) * 0xD1B54A32D192ED03 + 1) & _MASK

    def next_u64(self) -> int:
        self._s = (self._s + 0x9E3779B97F4A7C15) & _MASK
        z = self._s
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK
        return z ^ (z >> 31)

    def uniform(self) -> float:
        """In [0, 1), 53 bits."""
        return (self.next_u64() >> 11) / float(1 << 53)

    def below(self, n: int) -> int:
        return self.next_u64() % int(n)

    def permutation(self, n: int) -> list:
        """Fisher-Yates over range(n)."""
        out = list(range(n))
        for i in range(n - 1, 0, -1):
            j = self.below(i + 1)
            out[i], out[j] = out[j], out[i]
        return out
