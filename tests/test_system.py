"""End-to-end behaviour tests for the distributed training system.

The multi-device parts run on 8 forced host devices in a subprocess
(the main pytest process must keep seeing one device).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import model as M
from repro.optim import optimizers

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro import compat
    from repro.configs.base import ModelConfig, ParallelConfig
    from repro.core import attacks
    from repro.launch import steps
    from repro.models import model as M
    from repro.optim import optimizers

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      qk_norm=True)
    opt_cfg = optimizers.OptimizerConfig(learning_rate=5e-3, warmup_steps=2,
                                         total_steps=50)
    params = M.init_model(jax.random.key(0), cfg)
    opt = optimizers.init(opt_cfg, params)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 33), 0, 256,
                                          dtype=jnp.int32)}
    out = {}

    # Mode A: methods agree and train
    losses = {}
    for method in ("mean", "gather_mm", "rs_mm"):
        par = ParallelConfig(aggregation=method, microbatches=2)
        step, _ = steps.make_train_step_gspmd(cfg, par, opt_cfg, mesh)
        js = jax.jit(step)
        p, o = params, opt
        for _ in range(4):
            p, o, m = js(p, o, batch)
        losses[method] = float(m["loss"])
    out["modeA"] = losses

    # rs_mm == gather_mm (identical estimator)
    out["agree"] = abs(losses["rs_mm"] - losses["gather_mm"])

    # Mode A under attack: robust trains, mean stalls
    byz = attacks.ByzantineConfig(num_malicious=1, attack="additive",
                                  attack_kwargs=(("delta", 100.0),))
    att = {}
    for method in ("mean", "rs_mm"):
        par = ParallelConfig(aggregation=method)
        step, _ = steps.make_train_step_gspmd(cfg, par, opt_cfg, mesh,
                                              byzantine=byz)
        js = jax.jit(step)
        p, o = params, opt
        for _ in range(6):
            p, o, m = js(p, o, batch)
        att[method] = float(m["loss"])
    out["attacked"] = att

    # Mode B (fsdp): trains + robust under attack
    fs = {}
    for method, b in (("rs_mm", None), ("rs_mm", byz), ("mean", byz)):
        par = ParallelConfig(fsdp=True, aggregation=method, microbatches=2)
        build, _ = steps.make_train_step_fsdp(cfg, par, opt_cfg, mesh,
                                              byzantine=b)
        js = jax.jit(build(batch))
        p, o = params, opt
        for _ in range(6):
            p, o, m = js(p, o, batch)
        fs[f"{method}_{'att' if b else 'clean'}"] = float(m["loss"])
    out["fsdp"] = fs

    # serve: decode step under mesh
    cache = M.init_cache(cfg, 8, 16)
    dstep = steps.make_decode_step(cfg, mesh)
    tok = jnp.zeros((8, 1), jnp.int32)
    nxt, cache = jax.jit(dstep)(params, tok, cache)
    out["decode_shape"] = list(nxt.shape)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def dist():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"      # a child never competes for a chip
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mode_a_all_methods_train(dist):
    for method, loss in dist["modeA"].items():
        assert loss < 6.0, (method, loss)   # initial ~6.24


def test_rs_mm_equals_gather_mm(dist):
    assert dist["agree"] < 1e-4


def test_attacked_mean_stalls_robust_trains(dist):
    assert dist["attacked"]["rs_mm"] < 5.0
    assert dist["attacked"]["mean"] > dist["attacked"]["rs_mm"] + 0.5


def test_fsdp_trains_and_is_robust(dist):
    assert dist["fsdp"]["rs_mm_clean"] < 6.0
    assert dist["fsdp"]["rs_mm_att"] < 5.5
    assert dist["fsdp"]["mean_att"] > dist["fsdp"]["rs_mm_att"] + 0.4


def test_decode_step_shape(dist):
    assert dist["decode_shape"] == [8, 1]


# ---------------------------------------------------------------------------
# single-device end-to-end: overfit a tiny model
# ---------------------------------------------------------------------------

def test_single_device_overfit():
    cfg = ModelConfig(name="tiny", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=64)
    params = M.init_model(jax.random.key(0), cfg)
    opt_cfg = optimizers.OptimizerConfig(learning_rate=1e-2, warmup_steps=5,
                                         total_steps=200, name="adam")
    opt = optimizers.init(opt_cfg, params)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 33), 0, 64,
                                          dtype=jnp.int32)}

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(lambda q: M.loss_fn(q, cfg, batch))(p)
        p, o = optimizers.update(opt_cfg, p, g, o)
        return p, o, loss

    first = None
    for i in range(60):
        params, opt, loss = step(params, opt)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))
