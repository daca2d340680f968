"""Bounded FIFO memo over pure functions of their byte inputs.

Stdlib only, so the crypto oracle and the BLS shim both import it
without a cycle.
"""
from __future__ import annotations


class Memo:
    """Bounded FIFO memo over PURE primitives (sign, the scalar verify
    oracles and the validated point decodes are functions of their byte
    inputs, nothing else).  The test tier rebuilds identical blocks from
    the cached genesis state file after file, re-deriving byte-identical
    signatures, verdicts and points hundreds of times.  Only values are
    kept: a `compute` that raises stores nothing, so an invalid input is
    refused on every call."""

    _MISS = object()

    def __init__(self, cap: int = 1 << 14):
        self._store: dict = {}
        self._cap = cap

    def get(self, key, compute):
        hit = self._store.get(key, self._MISS)
        if hit is not self._MISS:
            return hit
        value = compute()
        if len(self._store) >= self._cap:
            self._store.pop(next(iter(self._store)), None)
        self._store[key] = value
        return value

    def __contains__(self, key) -> bool:
        return key in self._store
