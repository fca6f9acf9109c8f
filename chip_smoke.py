#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU, at stablelm-1.6b's
published widths (24 layers, d_model 2048, 32 heads, d_ff 5632, vocab
100352) with random weights from seed 0.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip paths only

One chip, in one process, in this order:

1. the compiled Pallas paged-decode kernel against the XLA block walk
   (``paged_attention_xla``) at the served pool's shapes;
2. ``FleetSpec`` -> ``ServingClient`` over a ``ContinuousBatchingEngine``
   pool: requests of mixed prompt lengths (one longer than the
   ``prompt_len`` bucket, so chunked prefill runs) are served, streamed
   and drained, each with exactly its ``max_new`` tokens;
3. every served token against a teacher-forced ``T.forward`` of the same
   padded sequence, and greedy-token agreement with the
   ``WindowedBaselineServer`` on the same params.

``--chips 4`` runs only what exists across chips: a
``PoolSpec(pipeline_stages=2)`` pool checked against the monolithic
forward under the same plan on one chip, and a few ``Trainer`` steps on
a (data=2, model=2) mesh checked against the same steps on one chip.

Earlier lines print smoke numbers (compile seconds, bytes, agreement);
they are not a benchmark.  The last line is one JSON object naming the
device.  There is no CPU fallback: without a TPU the script exits
non-zero and prints no result.  Any failed check raises, and the exit
code is then non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from dataclasses import replace
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
# |pallas - xla| <= KERNEL_ATOL + KERNEL_RTOL * |xla|: both walk the same
# bf16 pool in f32 and round once to the bf16 output
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# a served token's teacher-forced logit may sit at most this many
# standard deviations (of its logit row) below the row's maximum: bf16
# paths that differ only in summation order flip near-ties, a wrong
# kernel lands tokens several deviations down
REF_GAP = 0.25
# sharded vs one-chip training loss, relative
TRAIN_RTOL = 1e-2

# one-chip serving pool: 8 slots x 1024 tokens of bf16 KV (~1.6 GB)
POOL = dict(max_slots=8, max_window=8, max_wait_s=0.0, prompt_len=128,
            block_size=16, max_new=128, max_prompt_len=896)
# (prompt length, max_new) per request; 700 > prompt_len -> chunked
REQUESTS = [(5, 24), (33, 16), (96, 32), (128, 8), (17, 20), (250, 12),
            (700, 16), (64, 28)]
# stage-axis pool (recomputes the whole sequence every step)
STAGE_POOL = dict(max_slots=4, max_window=4, max_wait_s=0.0, prompt_len=32,
                  block_size=16, max_new=8)
STAGE_REQUESTS = [(9, 8), (32, 6), (20, 8), (3, 5)]
# sharded training: layers cut so the one-chip run holds f32 params and
# both AdamW moments (see CHANGES.md)
TRAIN = dict(layers=8, batch=4, seq=128, steps=3)


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found {devs[0].platform!r}, not "
                         f"a TPU; there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX found {len(devs)}")
    return devs


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def prompts(specs, vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, vocab, n).astype(np.int32), m)
            for n, m in specs]


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------
def check_paged_kernel(batch: int, rows: int, page: int, kvp: int, gp: int,
                       hd: int, mb: int, *, layers: int = 3,
                       interpret: bool = False, seed: int = 0) -> float:
    """The Pallas paged kernel against ``paged_attention_xla`` on random
    bf16 pools of ``layers`` layers, reading the middle one, with ragged
    lengths (one empty, one full).  Returns the largest absolute
    difference."""
    import jax.numpy as jnp

    from repro.kernels.ops import paged_attention_xla
    from repro.kernels.paged_attention import paged_attention_pallas

    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)
    q = normal(batch, kvp, gp, hd)
    k_pool = normal(layers, rows, page, kvp * hd)
    v_pool = normal(layers, rows, page, kvp * hd)
    lengths = rng.integers(1, mb * page + 1, batch).astype(np.int32)
    lengths[0], lengths[-1] = 0, mb * page
    table = -np.ones((batch, mb), np.int32)
    for b in range(batch):                 # the last row is the trash row
        live = -(-int(lengths[b]) // page)
        table[b, :live] = rng.choice(rows - 1, live, replace=False)
    args = (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lengths),
            jnp.int32(layers // 2))
    out = np.asarray(paged_attention_pallas(*args, interpret=interpret),
                     np.float32)
    ref = np.asarray(paged_attention_xla(*args), np.float32)
    assert np.isfinite(out).all(), "paged kernel: non-finite output"
    assert (out[0] == 0).all(), "paged kernel: empty sequence not exactly 0"
    err = np.abs(out - ref)
    bad = err > KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)
    assert not bad.any(), (f"paged kernel: {int(bad.sum())} elements off "
                           f"the XLA walk, max |diff| {err.max()}")
    return float(err.max())


# ---------------------------------------------------------------------------
# teacher-forced reference
# ---------------------------------------------------------------------------
def make_ref_scorer(cfg, plan, length: int, n_pos: int):
    """``(params, seq, first_pos, toks) -> (argmax [M], gap [M])``: the
    reference forward's greedy token at each of the ``M <= n_pos``
    positions from ``first_pos`` on, and how far below the row maximum
    ``toks`` sits, in units of the row's standard deviation.  One
    compiled program: ``seq`` pads to ``length``, positions to
    ``n_pos``."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    @jax.jit
    def score(params, seq, pos, toks):
        logits = T.forward(params, cfg, seq[None], plan).logits[0, pos]
        logits = logits.astype(jnp.float32)
        got = jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]
        gap = (logits.max(axis=1) - got) / logits.std(axis=1)
        return jnp.argmax(logits, axis=1), gap

    def run(params, seq, first_pos, toks):
        m = len(toks)
        buf = np.zeros(length, np.int32)
        buf[:len(seq)] = seq
        pos = np.full(n_pos, first_pos, np.int32)
        pos[:m] += np.arange(m, dtype=np.int32)
        tok = np.zeros(n_pos, np.int32)
        tok[:m] = toks
        best, gap = score(params, jnp.asarray(buf), jnp.asarray(pos),
                          jnp.asarray(tok))
        return np.asarray(best)[:m], np.asarray(gap)[:m]
    return run


def check_against_reference(scorer, params, served, pad_to) -> tuple:
    """``served``: [(prompt, tokens)].  Each prompt is left-padded with
    zeros to ``pad_to(len)`` as the server did; the reference sees the
    padded prompt plus every served token but the last.  Returns
    (greedy agreement, worst gap)."""
    agree = total = 0
    worst = 0.0
    for prompt, toks in served:
        padded = np.concatenate([np.zeros(pad_to(len(prompt)) - len(prompt),
                                          np.int32), prompt])
        seq = np.concatenate([padded, np.asarray(toks[:-1], np.int32)])
        best, gap = scorer(params, seq, len(padded) - 1, toks)
        agree += int((best == np.asarray(toks)).sum())
        total += len(toks)
        worst = max(worst, float(gap.max()))
        assert (gap <= REF_GAP).all(), (
            f"served tokens {np.asarray(toks)[gap > REF_GAP]} sit "
            f"{gap.max():.3f} logit deviations below the reference's "
            f"greedy choice (limit {REF_GAP})")
    return agree / total, worst


def drain_streams(client, reqs):
    """Submit every request, stream each to completion, drain; returns
    [(prompt, tokens)] after checking that no stream lost a token."""
    handles = [(p, m, client.submit(p, max_new=m)) for p, m in reqs]
    served = []
    for p, m, h in handles:
        toks = list(h.stream())
        assert h.admitted and not h.dropped, f"request {h.rid} dropped"
        assert len(toks) == m, (f"request {h.rid}: {len(toks)} tokens "
                                f"streamed, max_new={m}")
        served.append((p, toks))
    client.drain()
    for (p, toks), (_, _, h) in zip(served, handles):
        assert h.tokens == toks, f"request {h.rid}: stream != final tokens"
    return served


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------
def serve_phase(arch: str = ARCH, smoke: bool = False, pool=None,
                requests=None, seed: int = 0) -> None:
    import jax

    from repro.runtime.serve import ContinuousBatchingEngine, Request
    from repro.serving import FleetSpec, PoolSpec
    from repro.serving.spec import make_server

    pool = dict(POOL if pool is None else pool)
    requests = REQUESTS if requests is None else requests
    warnings.filterwarnings("error", message=r".*paged decode unavailable")
    spec = FleetSpec(pools=[PoolSpec("lm", ("tpu_v5e_bf16",),
                                     backend="engine", **pool)],
                     workload="transformer", arch=arch, smoke=smoke,
                     seq_len=pool["prompt_len"])
    t0 = time.perf_counter()
    client = spec.build(warm=False)
    log(f"build_s={time.perf_counter() - t0:.3f}")
    engine = client.engines["lm"]
    assert isinstance(engine, ContinuousBatchingEngine), type(engine)
    cfg, params = engine.cfg, engine.params
    log(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    kv = [st for st in engine.caches.values()]
    log(f"param_bytes={tree_bytes(params)} "
        f"kv_pool_bytes={sum(s.k_pool.nbytes + s.v_pool.nbytes for s in kv)}"
        f" slots={engine.max_slots} max_len={engine.max_len} "
        f"block_size={engine.block_size}")

    # 1. the kernel at the pool's own shapes
    st = kv[0]
    n_super, rows, page, width = st.k_pool.shape
    kvp, hd = cfg.num_kv_heads, cfg.resolved_head_dim()
    assert kvp * hd == width, (kvp, hd, width)
    gp = cfg.num_heads // cfg.num_kv_heads
    t0 = time.perf_counter()
    err = check_paged_kernel(engine.max_slots, rows, page, kvp, gp, hd,
                             engine.table_width,
                             interpret=jax.default_backend() != "tpu",
                             seed=seed)
    log(f"paged kernel vs xla walk at B={engine.max_slots} rows={rows} "
        f"P={page} KVp={kvp} gp={gp} hd={hd} MB={engine.table_width}: "
        f"max|diff|={err} (limit {KERNEL_ATOL}+{KERNEL_RTOL}|ref|), "
        f"{time.perf_counter() - t0:.3f}s incl. compile")

    # 2. warm the three programs the requests use (bucket admission,
    # chunked prefill, decode), then serve
    t0 = time.perf_counter()
    drain_streams(client, prompts([(3, 2), (pool["prompt_len"] + 1, 2)],
                                  cfg.vocab_size, seed + 1))
    log(f"warmup_compile_s={time.perf_counter() - t0:.3f} "
        f"(greedy admit + chunk + decode programs)")
    reqs = prompts(requests, cfg.vocab_size, seed + 2)
    t0 = time.perf_counter()
    served = drain_streams(client, reqs)
    tel = client.telemetry["pools"]["lm"]
    log(f"served {len(served)} requests / "
        f"{sum(len(t) for _, t in served)} tokens in "
        f"{time.perf_counter() - t0:.3f}s wall; prompt lengths "
        f"{[len(p) for p, _ in reqs]} (bucket {pool['prompt_len']})")
    log(f"pool telemetry: tokens_generated={tel['tokens_generated']} "
        f"decode_tokens_per_s={tel['decode_tokens_per_s']}")

    # 3. correctness: teacher-forced reference, then the windowed loop
    t0 = time.perf_counter()
    scorer = make_ref_scorer(cfg, engine.plan, engine.max_len,
                             max(m for _, m in requests))
    agree, worst = check_against_reference(scorer, params, served,
                                           engine.padded_prompt_len)
    log(f"engine vs teacher-forced forward: greedy agreement={agree} "
        f"worst gap={worst} logit std (limit {REF_GAP}), "
        f"{time.perf_counter() - t0:.3f}s incl. compile")

    short = [(p, t) for p, t in served if len(p) <= pool["prompt_len"]]
    win = make_server(cfg, params, PoolSpec(
        "windowed", ("tpu_v5e_bf16",), backend="windowed",
        max_slots=pool["max_slots"], prompt_len=pool["prompt_len"],
        max_new=max(len(t) for _, t in short)), warm=False)
    for i, (p, t) in enumerate(short):
        win.submit(Request(i, p, max_new=len(t)))
    while win.pending:
        win.flush()
    same = sum(int(a == b) for i, (_, t) in enumerate(short)
               for a, b in zip(win.done[i].output, t))
    log(f"engine vs WindowedBaselineServer: greedy agreement="
        f"{same / sum(len(t) for _, t in short)} over {len(short)} "
        f"requests")
    log(f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# four chips: stage-axis pool and sharded training
# ---------------------------------------------------------------------------
def stage_phase(arch: str = ARCH, smoke: bool = False, pool=None,
                requests=None, seed: int = 0) -> None:
    import jax

    from repro.serving import FleetSpec, PoolSpec
    from repro.serving.stage_executor import StageAxisEngine

    pool = dict(STAGE_POOL if pool is None else pool)
    requests = STAGE_REQUESTS if requests is None else requests
    spec = FleetSpec(pools=[PoolSpec("lm", ("tpu_v5e_bf16",),
                                     backend="engine", pipeline_stages=2,
                                     **pool)],
                     workload="transformer", arch=arch, smoke=smoke,
                     seq_len=pool["prompt_len"])
    t0 = time.perf_counter()
    client = spec.build(warm=False)
    engine = client.engines["lm"]
    assert isinstance(engine, StageAxisEngine), type(engine)
    log(f"stage pool build_s={time.perf_counter() - t0:.3f} plan="
        f"{[(s.name, s.start, s.end) for s in engine.plan.segments]}")
    for leaf in jax.tree_util.tree_leaves(engine._stacked):
        where = {sh.index[0].start: sh.device for sh in leaf.addressable_shards}
        assert len(set(where.values())) == 2 and set(where) == {0, 1}, where
    log(f"stage params: stage 0 on {where[0]}, stage 1 on {where[1]}")

    reqs = prompts(requests, engine.cfg.vocab_size, seed + 3)
    t0 = time.perf_counter()
    served = drain_streams(client, reqs)
    log(f"stage pool served {len(served)} requests / "
        f"{sum(len(t) for _, t in served)} tokens in "
        f"{time.perf_counter() - t0:.3f}s incl. compile")
    # the monolithic forward under the same plan, on one chip
    t0 = time.perf_counter()
    one_chip = jax.devices()[0]
    params = jax.device_put(engine.params, one_chip)
    scorer = make_ref_scorer(engine.cfg, engine.plan, engine.max_len,
                             max(m for _, m in requests))
    with jax.default_device(one_chip):
        agree, worst = check_against_reference(scorer, params, served,
                                               lambda n: n)
    log(f"stage pool vs one-chip T.forward (same plan): greedy agreement="
        f"{agree} worst gap={worst} logit std (limit {REF_GAP}), "
        f"{time.perf_counter() - t0:.3f}s incl. compile")


def train_phase(arch: str = ARCH, smoke: bool = False, train=None) -> None:
    import jax

    from repro.configs import get_config
    from repro.configs.base import MeshConfig, ShapeConfig, TrainConfig
    from repro.data.pipeline import lm_batch
    from repro.launch.mesh import make_mesh
    from repro.runtime.train_loop import Trainer

    train = dict(TRAIN if train is None else train)
    cfg = replace(get_config(arch, smoke=smoke), num_layers=train["layers"])
    shape = ShapeConfig("smoke", train["seq"], train["batch"], "train")
    mesh_cfg = MeshConfig((2, 2), ("data", "model"))
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=1)
    devs = jax.devices()
    meshes = {"2x2": make_mesh((2, 2), ("data", "model"), devices=devs[:4]),
              "1 chip": make_mesh((1, 1), ("data", "model"),
                                  devices=devs[:1])}
    losses = {}
    for name, mesh in meshes.items():
        t0 = time.perf_counter()
        tr = Trainer(cfg, shape, mesh_cfg, tc, mesh=mesh)
        state = tr.init_state()
        state, hist = tr.run(state, lambda s: lm_batch(cfg, shape, s),
                             train["steps"], log_every=1)
        losses[name] = np.array([h["loss"] for h in hist])
        del state
        log(f"train {name}: layers={cfg.num_layers} d_model={cfg.d_model} "
            f"batch={shape.global_batch}x{shape.seq_len} losses="
            f"{losses[name].tolist()} "
            f"{time.perf_counter() - t0:.3f}s incl. compile")
    a, b = losses["2x2"], losses["1 chip"]
    assert np.isfinite(a).all() and np.isfinite(b).all(), losses
    rel = np.abs(a - b) / np.abs(b)
    assert (rel <= TRAIN_RTOL).all(), (
        f"sharded loss off one chip by {rel.max()} (limit {TRAIN_RTOL})")
    log(f"train 2x2 vs 1 chip: max relative loss diff={rel.max()} "
        f"(limit {TRAIN_RTOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip paths")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache at {enable_compile_cache()}")
    log(f"device kind={devs[0].device_kind} count={len(devs)} "
        f"(smoke numbers, not a benchmark)")
    if args.chips == 4:
        train_phase()
        stage_phase()
    else:
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
