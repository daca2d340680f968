"""Spec-object machinery.

Each fork is a class (Phase0Spec -> AltairSpec -> ...); a *spec instance* is
(fork class × preset × config), carrying its own SSZ container classes
(preset values define vector/list shapes) and all spec functions as methods.
Class inheritance gives the reference's fork-overlay semantics
(/root/reference/pysetup/helpers.py:233 combine_spec_objects — later fork
wins) directly in Python, with `super()` for upgrade deltas.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager

from ..config import Config, load_config, load_preset
from ..utils import bls, spans

# the steps of `process_block`, each marked by a profiler span
# (utils/spans.py) wherever a fork class defines it
BLOCK_STEPS = {
    "process_block_header": "block.header",
    "process_randao": "block.randao",
    "process_eth1_data": "block.eth1_data",
    "process_operations": "block.operations",
    "process_sync_aggregate": "block.sync_aggregate",
    "process_withdrawals": "block.withdrawals",
    "process_execution_payload": "block.execution_payload",
    "process_execution_payload_header": "block.execution_payload_header",
}


def _spanned(method, name: str):
    @functools.wraps(method)
    def step(self, *args, **kwargs):
        with spans.span(name):
            return method(self, *args, **kwargs)
    return step


class BaseSpec:
    fork: str = "base"

    # sigpipe verdict map (sigpipe/verify.py block_scope); None outside a
    # pipeline window, when every seam call is a plain scalar verify
    _sigpipe_verdicts = None

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        for attr, name in BLOCK_STEPS.items():
            if attr in vars(cls):
                setattr(cls, attr, _spanned(vars(cls)[attr], name))

    def __init__(self, preset_name: str = "mainnet",
                 config: Config | None = None):
        self.preset_name = preset_name
        self.preset = load_preset(preset_name)
        self.config = config if config is not None else load_config(preset_name)
        # preset values become plain attributes (compile-time tier)
        for k, v in self.preset.items():
            setattr(self, k, v)
        self._caches: dict = {}
        self._build_constants()
        self._build_types()

    def _build_constants(self) -> None:
        pass

    def _build_types(self) -> None:
        pass

    # -- memoization across expensive pure accessors (the reference's
    #    cache_this layer, /root/reference/pysetup/spec_builders/phase0.py:47)
    def _cached(self, key, fn):
        cache = self._caches
        if key not in cache:
            cache[key] = fn()
        return cache[key]

    def is_post(self, fork_name: str) -> bool:
        """True if this spec builds on the given fork (MRO ancestry — the
        linear mainline order would misclassify feature forks like whisk,
        which branches off capella)."""
        mro_forks = [c.fork for c in type(self).__mro__
                     if hasattr(c, "fork")]
        return fork_name in mro_forks

    # -- signature verification seam -----------------------------------
    # Every per-operation signature check in the spec layer flows through
    # these two methods so a precomputed batch verdict (sigpipe/) can
    # stand in for the scalar call at the exact inline call site.  A map
    # miss — a check the collector didn't predict — falls back to the
    # scalar backend, so routing through the seam can never change
    # behavior.

    @contextmanager
    def install_sigpipe_verdicts(self, verdict_map):
        """Install a sigpipe VerdictMap on this spec instance for the
        duration (nestable: the previous map — usually None — is
        restored on exit).  Both the block window (sigpipe block_scope)
        and electra's epoch-boundary pending-deposit batch ride this."""
        previous = self._sigpipe_verdicts
        self._sigpipe_verdicts = verdict_map
        try:
            yield
        finally:
            self._sigpipe_verdicts = previous

    def bls_verify(self, pubkey, signing_root, signature) -> bool:
        verdicts = self._sigpipe_verdicts
        if verdicts is not None:
            v = verdicts.lookup((bytes(pubkey),), bytes(signing_root),
                                bytes(signature))
            if v is not None:
                return v
        return bls.Verify(pubkey, signing_root, signature)

    def bls_fast_aggregate_verify(self, pubkeys, signing_root,
                                  signature) -> bool:
        verdicts = self._sigpipe_verdicts
        if verdicts is not None:
            v = verdicts.lookup(tuple(bytes(pk) for pk in pubkeys),
                                bytes(signing_root), bytes(signature))
            if v is not None:
                return v
        return bls.FastAggregateVerify(pubkeys, signing_root, signature)
