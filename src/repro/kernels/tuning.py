"""Block-size + kernel-path autotuner for the MM-aggregation kernels.

The kernel's performance knobs are ``block_m`` (the lane tile, how many
coordinates share one VMEM residency), ``block_k`` (the K stream block;
``None`` streams the whole padded K axis as one block on the
single-pass path, or resolves to ``mm_aggregate.two_pass_block_k`` on
the two-pass path) and -- since the K-major two-pass kernel landed --
the *path* itself (``single`` | ``two_pass``).  The right choice
depends on the workload tuple

    (K, M, N, dtype)

because the kernel-body batch over N weight columns multiplies the
in-register working set: on the single-pass path the weighted-median
carry planes and the MAD deviation planes are (K_pad2, N, block_m) f32,
so large K*N wants a narrower block_m (and, past the VMEM budget, the
two-pass path) while small problems want the widest tile the M axis
supports (less grid overhead, better DMA efficiency).

Entry points:

  get_blocks(k, m, n, dtype)  -- cheap, shape-only: the cached
      autotuner winner's (block_m, block_k) if one exists, else a
      VMEM-budget heuristic.  Safe at trace time (never times).
  get_choice(k, m, n, dtype)  -- same lookup, full ``TuneChoice``
      including the kernel path (``path=None`` means "let
      ``mm_aggregate.auto_path`` decide").  This is what
      ``mm_aggregate.launch_plan`` (and hence the AggregationEngine)
      consults by default.
  autotune(k, m, n, dtype)    -- sweeps candidate (block_m, block_k[,
      path]) tuples on synthetic data, times the real launcher, caches
      the winner -- including the measured single<->two-pass crossover
      for K > 64 workloads -- and returns its (block_m, block_k).

The in-process cache (keyed by TuneKey) additionally persists across
processes when the ``REPRO_TUNING_CACHE`` environment variable names a
JSON file: cached entries are loaded lazily on the first lookup (a
corrupt or unreadable file silently falls back to the in-process
heuristic) and every autotune winner is written back atomically
(tmp file + os.replace), so concurrent writers can at worst lose an
update, never corrupt the file.  Entries are keyed by
(K, M, N, dtype, backend); the optional ``path`` field records the
kernel path the winner was measured on (absent/null = pre-two-pass
entry, auto-resolved).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import mm_aggregate as _mm

LANE = 128
# the per-core VMEM budget lives with the kernel geometry model
# (mm_aggregate.VMEM_BUDGET_BYTES); this alias keeps older imports alive
_VMEM_BUDGET_BYTES = _mm.VMEM_BUDGET_BYTES
_MAX_BLOCK_M = 1024

BlockChoice = Tuple[int, Optional[int]]   # (block_m, block_k)


class TuneChoice(NamedTuple):
    """A cached tuning decision.  ``path=None`` = no measured path
    (pre-two-pass cache entry or pure heuristic): the launch plan's
    ``auto_path`` crossover decides."""
    block_m: int
    block_k: Optional[int]
    path: Optional[str] = None


class TuneKey(NamedTuple):
    k: int
    m: int
    n: int
    dtype: str


_CACHE: Dict[TuneKey, TuneChoice] = {}

ENV_CACHE_PATH = "REPRO_TUNING_CACHE"
_persistent_loaded = False


def _key(k: int, m: int, n: int, dtype) -> TuneKey:
    return TuneKey(int(k), int(m), int(n), jnp.dtype(dtype).name)


# ---------------------------------------------------------------------------
# cross-process persistence
# ---------------------------------------------------------------------------

def cache_path() -> Optional[str]:
    """The persistent cache file ($REPRO_TUNING_CACHE), if configured."""
    return os.environ.get(ENV_CACHE_PATH) or None


def load_cache(path: Optional[str] = None, *, force: bool = True) -> int:
    """Merge the persistent JSON cache into the in-process cache.

    Returns the number of entries merged.  In-process entries win over
    file entries (a live autotune measurement beats a stale file).  A
    missing, corrupt, or wrong-schema file is treated as empty -- the
    heuristic fallback stays available -- never an error.
    """
    global _persistent_loaded
    if path is None:
        # only an env-path load satisfies (and marks) the lazy merge --
        # explicit-path loads must not suppress it
        if not force and _persistent_loaded:
            return 0
        _persistent_loaded = True
        path = cache_path()
    if not path:
        return 0
    try:
        with open(path) as f:
            payload = json.load(f)
        entries = payload["entries"]
        merged = 0
        for e in entries:
            try:
                if e.get("backend", "pallas") != "pallas":
                    continue
                key = TuneKey(int(e["k"]), int(e["m"]), int(e["n"]),
                              str(e["dtype"]))
                bk = e["block_k"]
                path = e.get("path")
                if path is not None:
                    path = str(path)
                    if path not in _mm.PATHS:
                        continue
                choice = TuneChoice(int(e["block_m"]),
                                    None if bk is None else int(bk), path)
            except (KeyError, TypeError, ValueError, AttributeError):
                continue    # skip the malformed entry, keep the rest
            if key not in _CACHE:
                _CACHE[key] = choice
                merged += 1
        return merged
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        return 0    # corrupt / unreadable file: heuristic fallback stays


def save_cache(path: Optional[str] = None) -> Optional[str]:
    """Atomically write the in-process cache (merged over any existing
    file entries) to the persistent JSON file; returns the path written
    or None when no path is configured."""
    path = path or cache_path()
    if not path:
        return None
    # merge existing file entries we don't override (other processes may
    # have tuned other shapes)
    load_cache(path, force=True)
    entries = [
        {"k": key.k, "m": key.m, "n": key.n, "dtype": key.dtype,
         "backend": "pallas", "block_m": bm, "block_k": bk, "path": path}
        for key, (bm, bk, path) in sorted(_CACHE.items())
    ]
    payload = {"version": 1, "entries": entries}
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)   # atomic on POSIX
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return path


def heuristic_blocks(k: int, m: int, n: int = 1,
                     dtype=jnp.float32) -> BlockChoice:
    """VMEM-budget fallback used when no autotune measurement is cached.

    The lane tile is sized against the kernel's own working-set models
    (``mm_aggregate.single_pass_vmem_bytes`` / ``two_pass_vmem_bytes``
    -- the same models ``launch_plan`` reports and ``repro.analysis``
    audits), so the heuristic can never pick a geometry whose resolved
    path overflows the budget by the model's own account.  Meshes below
    the two-pass crossover take the widest single-pass tile that fits;
    larger meshes get whichever path affords the wider tile -- in
    practice the two-pass kernel, whose working set stays bounded in K
    (``auto_path`` then resolves the path from the same models).
    Clamped to [128, 1024] and to the (lane-rounded) problem width so
    tiny M never over-pads; the K axis streams as one block on the
    single-pass path while the two-pass path derives its own
    power-of-two K block in mm_aggregate.
    """
    k, n = int(k), max(int(n), 1)
    m_lanes = max(LANE, ((int(m) + LANE - 1) // LANE) * LANE)
    cap = min(_MAX_BLOCK_M, m_lanes)

    def widest(model_bytes):
        bm = cap
        while bm > 0 and model_bytes(bm) > _VMEM_BUDGET_BYTES:
            bm -= LANE
        return bm

    bm_single = widest(lambda bm: _mm.single_pass_vmem_bytes(k, n, bm))
    if k < _mm._TWO_PASS_MIN_K:
        # small meshes stay single-pass (bit-stable with the
        # pre-two-pass kernel) even when the narrowest tile overflows
        return max(LANE, bm_single), None
    bk = _mm.two_pass_block_k(k)
    bm_two = widest(lambda bm: _mm.two_pass_vmem_bytes(
        k, n, bm, bk, _mm.two_pass_n_chunk(n, bm, bk)))
    return max(LANE, bm_single, bm_two), None


def get_blocks(k: int, m: int, n: int = 1, dtype=jnp.float32,
               backend: str = "pallas") -> BlockChoice:
    """Resolve block sizes for a workload shape: cached autotuner winner
    if one exists, else the heuristic.  Shape-only -- safe under jit
    tracing (never times, never touches array values)."""
    choice = get_choice(k, m, n, dtype, backend)
    return (choice.block_m, choice.block_k)


def get_choice(k: int, m: int, n: int = 1, dtype=jnp.float32,
               backend: str = "pallas") -> TuneChoice:
    """Full tuning decision for a workload shape, including the kernel
    path the winner was measured on (``path=None`` -> no measurement:
    ``mm_aggregate.auto_path`` decides).  Shape-only, trace-safe."""
    if backend != "pallas":
        return TuneChoice(*heuristic_blocks(k, m, n, dtype))
    load_cache(force=False)   # lazy one-time merge of $REPRO_TUNING_CACHE
    cached = _CACHE.get(_key(k, m, n, dtype))
    if cached is not None:
        return cached
    return TuneChoice(*heuristic_blocks(k, m, n, dtype))


def _as_choice(choice) -> TuneChoice:
    bm = int(choice[0])
    bk = None if choice[1] is None else int(choice[1])
    path = choice[2] if len(choice) > 2 else None
    if path is not None and path not in _mm.PATHS:
        raise ValueError(f"unknown kernel path {path!r}; known: {_mm.PATHS}")
    return TuneChoice(bm, bk, path)


def set_blocks(k: int, m: int, n: int, dtype, choice) -> None:
    """Pin a block choice (tests / precomputed tuning tables).  Accepts
    a (block_m, block_k) pair or a full (block_m, block_k, path)
    TuneChoice."""
    _CACHE[_key(k, m, n, dtype)] = _as_choice(choice)


def cache_size() -> int:
    return len(_CACHE)


def cache_state() -> tuple:
    """Hashable fingerprint of the tuning state that block/path
    resolution depends on.  Anything that caches a *compiled* program
    whose geometry came from ``get_choice`` (e.g. the scenario runner's
    executable cache) must key on this: a new autotune winner or a
    different $REPRO_TUNING_CACHE would otherwise serve a stale
    executable built for the old geometry."""
    load_cache(force=False)
    return (tuple(sorted(_CACHE.items())), cache_path())


def clear_cache() -> None:
    _CACHE.clear()


def _time_call_us(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))          # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def sublane_rows(dtype) -> int:
    """Row tile of one vreg for ``dtype``: a K block shorter than the
    padded K axis must be a multiple of it (8 rows for 32-bit, 16 for
    16-bit) or Mosaic refuses the BlockSpec."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def candidate_blocks(k: int, m: int, n: int = 1,
                     dtype=jnp.float32) -> Sequence[BlockChoice]:
    """Default single-pass sweep: lane tiles around the heuristic,
    full-K streaming plus one K-split when half the padded K axis is a
    whole number of sublane tiles."""
    bms = sorted({LANE, 256, 512, heuristic_blocks(k, m, n, dtype)[0]})
    m_lanes = max(LANE, ((int(m) + LANE - 1) // LANE) * LANE)
    bms = [bm for bm in bms if bm <= m_lanes] or [LANE]
    bks: list = [None]
    half = (int(k) + (int(k) % 2)) // 2
    if half % sublane_rows(dtype) == 0:
        bks.append(half)
    out = []
    for bm in bms:
        for bk in bks:
            if (bm, bk) not in out:
                out.append((bm, bk))
    return out


def candidate_choices(k: int, m: int, n: int = 1,
                      dtype=jnp.float32) -> Sequence[TuneChoice]:
    """Default crossover sweep: every single-pass candidate plus -- for
    meshes past the single-pass sweet spot -- two-pass variants (the
    auto K block and one split) so ``autotune`` measures the
    single<->two-pass crossover per (K, M, N, dtype) and caches it.
    Single-pass candidates whose modeled VMEM would overflow the budget
    by more than 4x are skipped rather than timed (they cannot run on
    hardware; timing them in interpret mode would reward a geometry the
    TPU cannot host)."""
    out = []
    for bm, bk in candidate_blocks(k, m, n, dtype):
        if _mm.single_pass_vmem_bytes(k, n, bm) <= \
                4 * _mm.VMEM_BUDGET_BYTES:
            out.append(TuneChoice(bm, bk, "single"))
    if int(k) >= _mm._TWO_PASS_MIN_K:
        bm0 = heuristic_blocks(k, m, n, dtype)[0]
        bk0 = _mm.two_pass_block_k(k)
        for bm in sorted({LANE, bm0}):
            out.append(TuneChoice(bm, bk0, "two_pass"))
            if bk0 >= 16:
                out.append(TuneChoice(bm, bk0 // 2, "two_pass"))
    return out or [TuneChoice(*heuristic_blocks(k, m, n, dtype))]


def autotune(k: int, m: int, n: int = 1, dtype=jnp.float32, *,
             candidates: Optional[Sequence] = None,   # BlockChoice|TuneChoice
             num_iters: int = 10,
             reps: int = 3,
             interpret: Optional[bool] = None,
             force: bool = False) -> BlockChoice:
    """Sweep (block_m, block_k[, path]) candidates on synthetic data,
    cache and return the fastest (the cached ``TuneChoice`` keeps the
    measured path; the returned pair stays (block_m, block_k) for
    callers that only size tiles).  Idempotent per (K, M, N, dtype)
    unless ``force``.  A candidate the backend refuses raises: every
    candidate offered is meant to run, so a refusal is a bug in the
    candidate list, not a slow geometry."""
    from repro.kernels import mm_aggregate as _mk  # full module, lazily

    key = _key(k, m, n, dtype)
    if not force and key in _CACHE:
        return (_CACHE[key].block_m, _CACHE[key].block_k)
    kx, ka = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (k, m)).astype(dtype)
    a = jax.random.uniform(ka, (k, n), minval=0.1, maxval=1.0,
                           dtype=jnp.float32)
    best: Optional[TuneChoice] = None
    best_us = float("inf")
    for cand in (candidates or candidate_choices(k, m, n, dtype)):
        cand = _as_choice(cand)

        def run(xv, av, _c=cand):
            return _mk.mm_aggregate_batched_2d(
                xv, av, num_iters=num_iters, block_m=_c.block_m,
                block_k=_c.block_k, path=_c.path, interpret=interpret)
        us = _time_call_us(jax.jit(run), x, a, reps=reps)
        if us < best_us:
            best, best_us = cand, us
    _CACHE[key] = best
    save_cache()        # best-effort persist of the measured winner
    return (best.block_m, best.block_k)
