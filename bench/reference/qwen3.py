"""Plain reference of data-parallel robust training of a Qwen3 decoder.

Written from the published Qwen3 description (Qwen3 Technical Report,
arXiv:2505.09388; the layer equations of Hugging Face's ``Qwen3Model``):

  h   = x + W_o Attn(RoPE(qknorm(W_q n1)), RoPE(qknorm(W_k n1)), W_v n1)
  x'  = h + W_down (silu(W_gate n2) * (W_up n2))

with n1 = RMSNorm(x), n2 = RMSNorm(h), grouped-query causal attention
(query head i reads key/value head i // (H / KV)), RoPE rotating the two
halves of each head, and qk-norm an RMSNorm over the head dimension.
Logits are RMSNorm(x_L) @ head over the published vocabulary; the loss
is the mean token cross-entropy.

The step of a K-agent data-parallel job: each agent's gradient of its
own loss, the last ``malicious`` agents' gradients shifted by ``delta``
on every coordinate (the paper's additive attack), an aggregate over the
K agents per coordinate (MM or mean), a global-norm clip, then Adam with
linear warm-up and cosine decay.  Everything is float32 at the
'highest' matmul precision.  The backward runs layer by layer
(``jax.vjp`` of one layer at a time, from stored layer inputs), so the
K agents' gradients of one layer are aggregated before the next layer's
are formed and the whole fits beside the state on one chip.

``matmul="int8"`` is the control: every matmul operand rounded to int8
with one absmax scale per tensor (the gradient passes straight through
the rounding).  ``fault`` plants a fault in the reference put in the
program's place: ``"half_batch"``, each agent's loss and gradient over
the first half of its rows only; ``"no_exchange"``, no exchange between
the agents, so the step applies the first agent's own gradient.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import mm

LAYER_KEYS = ("ln1", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
              "attn/q_norm", "attn/k_norm", "ln2", "mlp/w_gate", "mlp/w_up",
              "mlp/w_down")


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """{path: (shape, dtype)} of the trained parameters: per-layer
    tensors stacked over the layers, embedding and head at the padded
    vocabulary (the padding never reaches the logits)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    ff, v = cfg["intermediate_size"], padded_vocab(cfg)
    per = {"ln1": (d,), "attn/wq": (d, h * hd), "attn/wk": (d, kv * hd),
           "attn/wv": (d, kv * hd), "attn/wo": (h * hd, d),
           "attn/q_norm": (hd,), "attn/k_norm": (hd,), "ln2": (d,),
           "mlp/w_gate": (d, ff), "mlp/w_up": (d, ff), "mlp/w_down": (ff, d)}
    out = {"embed": ((v, d), "float32"), "ln_f": ((d,), "float32")}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((d, v), "float32")
    for k, s in per.items():
        out[f"blocks/{k}"] = ((L,) + s, "float32")
    return out


def _quant(a):
    """int8 with one absmax scale, gradient straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(a / s), -127.0, 127.0) * s
    return a + jax.lax.stop_gradient(q - a)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(p, x, *, cfg_t, int8):
    h, kv, hd, eps, theta = cfg_t
    q8 = _quant if int8 else (lambda a: a)

    def mmul(a, w):
        return q8(a) @ q8(w)

    b, s, _ = x.shape
    n1 = _rms(x, p["ln1"], eps)
    q = mmul(n1, p["attn/wq"]).reshape(b, s, h, hd)
    k = mmul(n1, p["attn/wk"]).reshape(b, s, kv, hd)
    v = mmul(n1, p["attn/wv"]).reshape(b, s, kv, hd)
    q = _rope(_rms(q, p["attn/q_norm"], eps), theta)
    k = _rope(_rms(k, p["attn/k_norm"], eps), theta)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q8(probs), q8(v)).reshape(b, s, h * hd)
    x = x + mmul(o, p["attn/wo"])
    n2 = _rms(x, p["ln2"], eps)
    f = jax.nn.silu(mmul(n2, p["mlp/w_gate"])) * mmul(n2, p["mlp/w_up"])
    return x + mmul(f, p["mlp/w_down"])


def _head_loss(ln_f, head, x, labels, *, eps, vocab, int8):
    q8 = _quant if int8 else (lambda a: a)
    logits = q8(_rms(x, ln_f, eps)) @ q8(head[:, :vocab])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnames=("cfg_t", "int8"))
def _layer_fwd(p, x, *, cfg_t, int8):
    return _layer(p, x, cfg_t=cfg_t, int8=int8)


@functools.partial(jax.jit, static_argnames=("cfg_t", "int8"))
def _layer_bwd(p, x, g, *, cfg_t, int8):
    return jax.vjp(lambda p_, x_: _layer(p_, x_, cfg_t=cfg_t, int8=int8),
                   p, x)[1](g)


_head_grad = jax.jit(jax.value_and_grad(_head_loss, argnums=(0, 1, 2)),
                     static_argnames=("eps", "vocab", "int8"))


@jax.jit
def _take_layer(blocks, l):
    return {k: v[l] for k, v in blocks.items()}


@jax.jit
def _embed_grad(shape_like, rows, dx):
    return jnp.zeros_like(shape_like).at[rows].add(dx)


class Reference:
    """The K-agent step, run for a few steps from the seed's inputs."""

    def __init__(self, cfg: dict, job: dict, *, matmul: str = "f32",
                 fault: str = ""):
        if matmul not in ("f32", "int8"):
            raise ValueError(f"matmul must be f32 or int8, got {matmul!r}")
        if fault not in ("", "half_batch", "no_exchange"):
            raise ValueError(f"unknown fault {fault!r}")
        self.cfg, self.job = cfg, job
        self.int8 = matmul == "int8"
        self.fault = fault
        self.L = cfg["num_hidden_layers"]
        self.eps = float(cfg["rms_norm_eps"])
        self.vocab = cfg["vocab_size"]
        self.tied = bool(cfg["tie_word_embeddings"])
        self.cfg_t = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"], self.eps, float(cfg["rope_theta"]))

    # -- one step's per-agent gradients, aggregated layer by layer --------

    def _aggregate(self, per_agent):
        """K per-agent gradients -> attacked -> aggregated."""
        job = self.job
        if self.fault == "no_exchange":
            return per_agent[0]
        return mm.aggregate(
            per_agent, method="mean" if job["aggregation"] == "mean" else "mm",
            nmal=int(job["malicious"]), delta=float(job["delta"]),
            iters=int(job["agg_iters"]))

    def grads(self, params, tokens):
        """Mean loss over agents and the aggregated gradient {path: g}."""
        job = self.job
        k = int(job["agents"])
        rows = tokens.shape[0] // k
        use = rows // 2 if self.fault == "half_batch" else rows
        agent_tok = [tokens[i * rows:i * rows + use] for i in range(k)]
        blocks = {n: params[f"blocks/{n}"] for n in LAYER_KEYS}
        layers = [_take_layer(blocks, l) for l in range(self.L)]
        kw = dict(cfg_t=self.cfg_t, int8=self.int8)
        head = params["embed"].T if self.tied else params["head"]
        xs: List[List] = []
        for t in agent_tok:
            x = jnp.take(params["embed"], t[:, :-1], axis=0)
            hist = [x]
            for l in range(self.L):
                x = _layer_fwd(layers[l], x, **kw)
                hist.append(x)
            xs.append(hist)
        losses, g_lnf, g_head, dx = [], [], [], []
        for t, hist in zip(agent_tok, xs):
            loss, (gl, gh, gx) = _head_grad(
                params["ln_f"], head, hist[-1], t[:, 1:], eps=self.eps,
                vocab=self.vocab, int8=self.int8)
            losses.append(loss)
            g_lnf.append(gl)
            g_head.append(gh)
            dx.append(gx)
        del gh
        agg = {"ln_f": self._aggregate(g_lnf)}
        if not self.tied:
            agg["head"] = self._aggregate(g_head)
            g_head = None
        per_layer = []
        for l in reversed(range(self.L)):
            gp = []
            for a in range(k):
                g_l, dx[a] = _layer_bwd(layers[l], xs[a][l], dx[a], **kw)
                gp.append(g_l)
            per_layer.append({n: self._aggregate([g[n] for g in gp])
                              for n in LAYER_KEYS})
        per_layer.reverse()
        for n in LAYER_KEYS:
            agg[f"blocks/{n}"] = jnp.stack([pl[n] for pl in per_layer])
        del per_layer, xs, hist, layers
        emb = []
        for a, t in enumerate(agent_tok):
            g = _embed_grad(params["embed"], t[:, :-1], dx[a])
            if self.tied:
                g = g + g_head[a].T
            emb.append(g)
        del g
        agg["embed"] = self._aggregate(emb)
        return float(np.mean([float(v) for v in losses])), agg


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up over ``warmup_steps``, then cosine decay to a
    tenth of ``learning_rate`` at ``total_steps``."""
    warm = min(1.0, (step + 1.0) / max(opt["warmup_steps"], 1))
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    return opt["learning_rate"] * warm * (0.1 + 0.45 * (1 + math.cos(math.pi * frac)))


@jax.jit
def _sq(x):
    return jnp.sum(jnp.square(x))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, scale, lr, b1, b2, eps, t):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return p - lr * mh / (jnp.sqrt(vh) + eps), m, v


def run(cfg: dict, job: dict, seed: int, steps: int, **kw) -> dict:
    """``steps`` steps from the seed: each step's mean loss, the per-leaf
    norms of the first clipped gradient and of the parameters' change
    after the last step."""
    opt = job["optimizer"]
    shapes = param_shapes(cfg)
    ref = Reference(cfg, job, **kw)
    feed = weights.token_fn(seed, int(job["agents"]) * int(job["seqs_per_agent"]),
                            int(job["seq_len"]) + 1, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        params = dict(weights.make_params(seed, shapes))
        # Adam's moments wait on the host while the next step's gradients
        # are formed, so that those fit on the chip beside the parameters
        m, v = {}, {}
        losses, g1 = [], None
        for i in range(steps):
            loss, g = ref.grads(params, feed(i))
            losses.append(loss)
            norm = math.sqrt(sum(float(_sq(x)) for x in g.values()))
            clip = float(opt["grad_clip"])
            scale = min(1.0, clip / max(norm, 1e-9)) if clip > 0 else 1.0
            if g1 is None:
                g1 = {p: math.sqrt(float(_sq(x))) * scale for p, x in g.items()}
            for p in params:
                mp = jnp.asarray(m[p]) if p in m else jnp.zeros_like(params[p])
                vp = jnp.asarray(v[p]) if p in v else jnp.zeros_like(params[p])
                params[p], mp, vp = _adam(
                    params[p], mp, vp, g[p], scale, lr_at(opt, i),
                    opt["beta1"], opt["beta2"], opt["eps"], float(i + 1))
                if i + 1 < steps:
                    m[p], v[p] = np.asarray(mp), np.asarray(vp)
                del mp, vp, g[p]
            del g
        del m, v
        p0 = weights.make_params(seed, shapes)
        change = {p: math.sqrt(float(_sq(params[p] - p0[p]))) for p in params}
    return {"losses": losses, "grad_norms": g1, "change_norms": change}
