"""The program's profiler spans (utils/spans.py): every name the code
opens is declared, the block steps of every fork class carry one span
each, a span is a no-op context in a process that never imported JAX,
and spans leave the METRICS counters alone."""
import ast
import os
import subprocess
import sys

import pytest

import consensus_specs_tpu
from consensus_specs_tpu.specs import available_forks, base, get_spec
from consensus_specs_tpu.utils import spans

PACKAGE = os.path.dirname(consensus_specs_tpu.__file__)


def _opened_names() -> set:
    """The string literals passed to `span(...)` / `spans.span(...)`
    anywhere in the package."""
    names = set()
    for folder, _dirs, files in os.walk(PACKAGE):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(folder, f)) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call) and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        and getattr(node.func, "id",
                                    getattr(node.func, "attr", None))
                        == "span"):
                    names.add(node.args[0].value)
    return names


def test_every_opened_span_is_declared_and_every_declared_one_opened():
    opened = _opened_names() | set(base.BLOCK_STEPS.values())
    assert opened == set(spans.SPANS)


@pytest.mark.parametrize("name,declared", [
    ("ssz.copy", True), ("dispatch:ops.msm", True),
    ("dispatch:ssz.merkle_sweep", True), ("dispatch:no.such_site", False),
    ("dispatch:txn.mutate", False),         # a barrier, not a dispatch
    ("state_transition", False)])
def test_declared(name, declared):
    assert spans.declared(name) is declared


@pytest.mark.parametrize("fork", available_forks())
def test_block_steps_carry_one_span_each(fork):
    cls = type(get_spec(fork, "minimal"))
    for attr in base.BLOCK_STEPS:
        for klass in cls.__mro__:
            method = vars(klass).get(attr)
            if method is not None:
                # wrapped once where the class defines it, never twice
                assert hasattr(method, "__wrapped__"), (klass, attr)
                assert not hasattr(method.__wrapped__, "__wrapped__")


def test_span_without_jax_is_a_shared_no_op():
    code = ("import sys\n"
            "from consensus_specs_tpu.utils import spans\n"
            "with spans.span('ssz.copy'):\n"
            "    pass\n"
            "assert spans.span('epoch.tail') is spans.span('ssz.copy')\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.path.dirname(PACKAGE))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_spans_record_no_counter():
    from consensus_specs_tpu.sigpipe import METRICS
    METRICS.reset()
    for name in spans.SPANS:
        with spans.span(name):
            pass
    assert METRICS.snapshot() == {}
