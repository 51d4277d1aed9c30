"""The Mode A train step names its phases: every compiled instruction
that does work carries one of ``steps.STEP_PHASES`` in its ``op_name``,
and the scopes leave the compiled program itself as it was."""

import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro import compat
from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import attacks
from repro.launch import steps
from repro.models import model as M
from repro.optim import optimizers

WORK = ("while", "fusion", "dot", "convolution", "custom-call")
_LINE = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _unscoped_ok(name, opcode, op_name):
    """Work instructions the CPU compile leaves outside every scope, by
    opcode.  Two fusions: ``jit(step)/pow``, the RoPE frequency table
    (``layers.rope_freqs``), a constant JAX computes outside the scanned
    layers and every scope; and ``%wrapped_*`` without metadata, where
    the CPU compiler wraps a lone instruction in a fusion of its own."""
    return opcode == "fusion" and (
        op_name == "jit(step)/pow"
        or (not op_name and name.startswith("%wrapped_")))


CASES = {
    "rs_mm-kernel-attack": dict(aggregation="rs_mm", use_kernel=True,
                                malicious=1),
    "mean": dict(aggregation="mean", use_kernel=False, malicious=0),
}


def _compiled_text(aggregation, use_kernel, malicious):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64)
    opt_cfg = optimizers.OptimizerConfig()
    par = ParallelConfig(fsdp=False, microbatches=1, aggregation=aggregation,
                         use_kernel=use_kernel, agg_num_iters=2)
    byz = attacks.ByzantineConfig(
        num_malicious=malicious, attack="additive",
        attack_kwargs=(("delta", 10.0),)) if malicious else None
    step, _ = steps.make_train_step_gspmd(cfg, par, opt_cfg, mesh, byz,
                                          k_agents=4)
    params = jax.eval_shape(lambda: M.init_model(jax.random.key(0), cfg))
    opt = jax.eval_shape(lambda: optimizers.init(opt_cfg, params))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 17), jnp.int32)}
    return jax.jit(step).lower(params, opt, batch).compile().as_text()


def _instructions(text):
    """(name, result type, opcode, op_name) of every instruction."""
    out = []
    for line in text.splitlines():
        m = _LINE.match(line)
        if m:
            on = _OP_NAME.search(line)
            out.append(m.groups() + (on.group(1) if on else "",))
    return out


def _scope(op_name):
    parts = op_name.split("/")
    return next((p for p in parts if p in steps.STEP_PHASES), None)


@pytest.fixture(scope="module", params=sorted(CASES))
def compiled(request):
    """The case's step compiled with its scopes, and with
    ``jax.named_scope`` made a no-op while it traces."""
    kw = CASES[request.param]
    scoped = _compiled_text(**kw)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = _compiled_text(**kw)
    return request.param, scoped, plain


def test_every_work_instruction_names_its_phase(compiled):
    case, scoped, _ = compiled
    seen, stray = set(), []
    for name, _, opcode, op_name in _instructions(scoped):
        if opcode not in WORK:
            continue
        scope = _scope(op_name)
        if scope is not None:
            seen.add("backward" if scope == "agent_grads"
                     and "transpose(" in op_name else scope)
        elif not _unscoped_ok(name, opcode, op_name):
            stray.append((name, opcode, op_name))
    assert not stray, stray[:10]
    want = {"agent_grads", "backward", "aggregate", "optimizer"}
    if CASES[case]["malicious"]:
        want.add("attack")
    assert want <= seen


def test_scopes_leave_the_compiled_program_unchanged(compiled):
    _, scoped, plain = compiled
    count = lambda text: collections.Counter(  # noqa: E731
        (opcode, typ) for _, typ, opcode, _ in _instructions(text))
    assert count(scoped) == count(plain)
    assert "agent_grads" in scoped and "agent_grads" not in plain
