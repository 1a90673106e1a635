#!/usr/bin/env python3
"""Smoke run on a TPU: the served path and the trainer start and give sane
results.  A smoke run, not a benchmark: its times include one process, one
warm-up and no repeats.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: data-parallel training only

One chip, one process, qwen1.5-0.5b at its full published width (24 layers,
d_model 1024, 16/16 heads, vocab 151936), random weights from a seed:

1. serve the fp QAT student, then the packed ternary student, through
   ``repro.launch.serve``'s own ``build_engine`` and ``run_load`` (TCP
   front-end -> AsyncEngine -> fused step): 8 greedy requests, 64-token
   prompts, 32 new tokens, ``max_batch`` 8, no supervisor.  The load runs
   twice on the same engine; the first run compiles, the second gives the
   wall tok/s and must repeat the first run's tokens.  Every request must
   end ``stop`` or ``length`` with the right token count, with no step
   failure, retry or quarantine, and ``attn_impl`` must resolve to fused;
2. check the fused kernels against the gather path (fp student, first 8
   tokens).  Each kernel must match its gather reference within
   ``KERNEL_TOL`` at the served shapes.  Then both paths are teacher-forced
   on the prompts and the tokens the fused engine served, through the
   prefill-chunk and the decode kernels, and their logits must agree
   within ``LOGIT_TOL`` at every position.  Each served token must lie
   within ``LOGIT_TOL`` of the gather path's top logit; where it is not the
   gather argmax, that deficit is printed;
3. three steps of ``repro.launch.train`` at ``--dp 1 --tp 1``, batch 8,
   sequence 64, QAT: the losses must be finite.

``--chips 4`` runs ``repro.launch.train`` at ``--dp 4`` and at ``--dp 1``
on the same global batch for the same three steps, and nothing else: the
losses and the global gradient norms must agree within ``DP_RTOL``.

Without a TPU, or outside a checkout of the repo, the script exits 1 and
prints no result.  Compiled programs
go to JAX's persistent cache (``repro.launch.compile_cache``), so a second
run in the same checkout compiles less.  The last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import gc
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402  (after the path set-up, like every import below)
import jax.numpy as jnp  # noqa: E402

ARCH = "qwen1.5-0.5b"
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 64, 32
SERVE_ARGV = ["--arch", ARCH, "--requests", str(N_REQUESTS),
              "--prompt-len", str(PROMPT_LEN),
              "--max-tokens", str(NEW_TOKENS), "--max-batch", "8"]
CHECK_TOKENS = 8          # fused-vs-gather: first tokens of each request
TRAIN_ARGV = ["--arch", ARCH, "--tp", "1", "--steps", "3", "--batch", "8",
              "--seq", "64", "--quant", "qat", "--log-every", "1"]
# fused vs gather.  Kernel against reference, float32 pools: largest gap.
KERNEL_TOL = 1e-3
# Teacher-forced model: largest logit gap, and largest deficit of a served
# token below the gather top logit
LOGIT_TOL = 0.25
# dp=4 vs dp=1: largest relative gap of a step's loss or gradient norm
DP_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, read per
    phase from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("_", 1)[-1]] += 1

    def phase(self, name: str, t0: float, before: tuple) -> None:
        secs, hits, misses = before
        peak = jax.devices()[0].memory_stats() or {}
        print(f"[phase] {name}: wall {time.perf_counter() - t0:.1f} s, "
              f"compile {self.seconds - secs:.1f} s "
              f"(cache hits {self.events['hits'] - hits}, "
              f"misses {self.events['misses'] - misses}), "
              f"peak_bytes_in_use {peak.get('peak_bytes_in_use', 'n/a')}",
              flush=True)

    def mark(self) -> tuple:
        return self.seconds, self.events["hits"], self.events["misses"]


def request_tokens(events) -> list:
    return [e["token"] for e in events if e.get("token", -1) >= 0]


def serve_student(meter: CompileMeter, packed: bool):
    """One student through the served path; returns (engine, prompts,
    tokens per request)."""
    from repro.launch import serve

    name = "packed" if packed else "fp"
    t0, before = time.perf_counter(), meter.mark()
    args = serve.parse_args(SERVE_ARGV + (["--packed"] if packed else []))
    eng = serve.build_engine(args)
    check(eng.attn_impl == "fused",
          f"{name}: attn_impl resolved to {eng.attn_impl!r}, want 'fused'")
    print(f"[serve:{name}] attn_impl = {eng.attn_impl}")
    draw = serve.make_prompt_source(args)
    prompts = [draw() for _ in range(N_REQUESTS)]   # run_load's own prompts
    runs = []
    for _ in range(2):
        res = asyncio.run(serve.run_load(eng, args))
        check(res.engine is eng, f"{name}: the engine was replaced mid-run")
        reasons = [evs[-1].get("finish_reason") if evs else None
                   for evs in res.events]
        toks = [request_tokens(evs) for evs in res.events]
        for i, (why, t) in enumerate(zip(reasons, toks)):
            check(why in ("stop", "length"),
                  f"{name}: request {i} finished {why!r}")
            check(len(t) == NEW_TOKENS if why == "length"
                  else 1 <= len(t) <= NEW_TOKENS,
                  f"{name}: request {i} ({why}) gave {len(t)} tokens")
        runs.append((toks, reasons, res))
    (toks, _, _), (toks2, reasons, res) = runs
    check(toks2 == toks, f"{name}: the second greedy run gave other tokens")
    st = eng.stats()
    check(st.step_failures == 0 and st.step_retries == 0
          and st.quarantines == 0,
          f"{name}: step_failures={st.step_failures} "
          f"retries={st.step_retries} quarantines={st.quarantines}")
    print(f"[serve:{name}] {N_REQUESTS}/{N_REQUESTS} requests finished "
          f"({dict(collections.Counter(reasons))}), 0 step failures/"
          f"retries/quarantines; warm run "
          f"{res.tokens} tokens in {res.seconds:.3f} s = "
          f"{res.tokens / res.seconds:.1f} wall tok/s (smoke, not a "
          "benchmark)")
    meter.phase(f"serve {name}", t0, before)
    return eng, prompts, toks


def forced_logits(eng, impl: str, prompts, fed):
    """Logits at each checked position when every row is fed its prompt and
    then ``fed`` (teacher forcing), with the paged attention of ``impl``:
    the prompt through prefill-chunk steps, then one decode step per token,
    as a served request runs.  Returns [rows, 1 + len(fed[0]), V] logits,
    cast from the compute dtype to float32."""
    model, scfg = eng.model, eng.scfg
    rows, width, chunk = len(prompts), scfg.blocks_per_slot, scfg.prefill_chunk
    check(PROMPT_LEN % chunk == 0, f"prompt {PROMPT_LEN} % chunk {chunk}")
    # row b owns blocks 1 + b * width ..; block 0 is the trash block
    tables = 1 + jnp.arange(rows * width, dtype=jnp.int32).reshape(rows, -1)
    full = lambda v: jnp.full((rows,), v, jnp.int32)

    @jax.jit
    def run(params, prompt, fed):
        cache = model.init_cache(params, 1 + rows * width,
                                 scfg.kv_block_size,
                                 jnp.dtype(scfg.cache_dtype))
        for s in range(0, PROMPT_LEN, chunk):
            logits, cache = model.decode_chunk(
                params, prompt[:, s:s + chunk], cache, full(s), full(chunk),
                tables, attn_impl=impl)

        def step(cache, x):
            tok, pos = x
            out, cache = model.decode_step(params, tok, cache, full(pos),
                                           block_tables=tables,
                                           attn_impl=impl)
            return cache, out

        _, rest = jax.lax.scan(step, cache, (
            fed.T, PROMPT_LEN + jnp.arange(fed.shape[1], dtype=jnp.int32)))
        return jnp.concatenate([logits[:, -1:], rest.swapaxes(0, 1)],
                               1).astype(jnp.float32)

    return run(eng.params, jnp.asarray(prompts, jnp.int32),
               jnp.asarray(fed, jnp.int32))


def kernels_vs_refs(eng) -> float:
    """The decode and prefill-chunk kernels, through the wrappers the model
    calls, against their gather references (``kernels/*/ref.py``) at the
    served shapes and dtypes, on seeded inputs: rows at staggered depths,
    the decode writing past the prompt, whole chunks.  Returns the largest
    gap over outputs and both pools."""
    from repro.kernels.paged_attention.ops import paged_attention_decode
    from repro.kernels.paged_attention.ref import paged_attention_decode_ref
    from repro.kernels.paged_prefill.ops import paged_prefill_chunk
    from repro.kernels.paged_prefill.ref import paged_prefill_chunk_ref

    cfg, scfg = eng.cfg, eng.scfg
    rows, width, bs = scfg.max_batch, scfg.blocks_per_slot, scfg.kv_block_size
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = scfg.prefill_chunk
    g, cdt = hq // hkv, jnp.dtype(cfg.compute_dtype)
    pool = (1 + rows * width, hkv, bs, dh)
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    draw = lambda *shape: jax.random.normal(next(ks), shape, cdt)
    kp, vp = (draw(*pool).astype(scfg.cache_dtype) for _ in range(2))
    tables = 1 + jnp.arange(rows * width, dtype=jnp.int32).reshape(rows, -1)
    row = jnp.arange(rows, dtype=jnp.int32)
    q, kn, vn = draw(rows, hq, dh), draw(rows, hkv, dh), draw(rows, hkv, dh)
    idx = PROMPT_LEN + 3 * row                 # within the rows' tables
    qc, kc, vc = draw(rows, t, hq, dh), draw(rows, t, hkv, dh), \
        draw(rows, t, hkv, dh)
    start, lens = 1 + 7 * row, jnp.full((rows,), t, jnp.int32)
    heads = lambda a: a.transpose(0, 2, 1, 3)              # [B, H, T, Dh]
    with jax.default_matmul_precision("highest"):
        out, *pools = paged_attention_decode(q, kn, vn, kp, vp, tables, idx)
        ref, *ref_pools = paged_attention_decode_ref(
            q.reshape(rows, hkv, g, dh), kn, vn, kp, vp, tables, idx,
            dh ** -0.5)
        pairs = [(out, ref.reshape(out.shape)), *zip(pools, ref_pools)]
        out, *pools = paged_prefill_chunk(qc, kc, vc, kp, vp, tables, start,
                                          lens)
        ref, *ref_pools = paged_prefill_chunk_ref(
            heads(qc.reshape(rows, t, hkv, g * dh)).reshape(
                rows, hkv, t * g, dh),
            heads(kc), heads(vc), kp, vp, tables, start, lens, dh ** -0.5)
        ref = heads(ref.reshape(rows, hkv, t, g * dh)).reshape(out.shape)
        pairs += [(out, ref), *zip(pools, ref_pools)]
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in pairs)


def fused_vs_gather(meter: CompileMeter, eng, prompts, served) -> None:
    """Each kernel against its reference, within KERNEL_TOL; then both
    attention paths teacher-forced on the fused engine's own tokens, their
    logits within LOGIT_TOL at every position, and each served token within
    LOGIT_TOL of the gather path's top logit."""
    t0, before = time.perf_counter(), meter.mark()
    kernel_gap = kernels_vs_refs(eng)
    print(f"[fused-vs-gather] kernels vs their gather references: largest "
          f"gap {kernel_gap:.4g} (tolerance {KERNEL_TOL})")
    n = min(CHECK_TOKENS, *map(len, served))
    got = [t[:n] for t in served]
    fed = [t[:n - 1] for t in got]
    fused = forced_logits(eng, "fused", prompts, fed)
    gather = forced_logits(eng, "gather", prompts, fed)
    gap = jnp.max(jnp.abs(fused - gather), axis=-1)            # [rows, n]
    chosen = jnp.take_along_axis(gather, jnp.asarray(got)[..., None], -1)
    deficit = jnp.max(gather, axis=-1) - chosen[..., 0]        # >= 0
    gap, deficit = jax.device_get((gap, deficit))
    top = jax.device_get(jnp.argmax(gather, axis=-1))
    for i, row in enumerate(got):
        parts = [j for j in range(n) if row[j] != top[i, j]]
        print(f"[fused-vs-gather] request {i}: max logit gap "
              f"{gap[i].max():.4g}; served token is the gather argmax at "
              f"{n - len(parts)}/{n}"
              + "".join(f"; token {j}: {row[j]} vs {top[i, j]}, deficit "
                        f"{deficit[i, j]:.4g}" for j in parts))
    worst, worst_deficit = float(gap.max()), float(deficit.max())
    print(f"[fused-vs-gather] teacher-forced over {len(got)} requests x {n} "
          f"tokens: max |fused - gather| logit gap {worst:.4g}, largest "
          f"served-token deficit {worst_deficit:.4g} (tolerance {LOGIT_TOL})")
    check(kernel_gap <= KERNEL_TOL, f"a kernel parts from its reference by "
                                    f"{kernel_gap:.4g} > {KERNEL_TOL}")
    check(worst <= LOGIT_TOL, f"fused and gather logits part by {worst:.4g} "
                              f"> {LOGIT_TOL}")
    check(worst_deficit <= LOGIT_TOL,
          f"a served token lies {worst_deficit:.4g} below the gather top "
          f"logit (> {LOGIT_TOL})")
    meter.phase("fused vs gather", t0, before)


def train_steps(meter: CompileMeter, dp: int) -> list:
    """Three trainer steps; returns each step's metrics."""
    from repro.launch import train

    t0, before = time.perf_counter(), meter.mark()
    steps = train.main(TRAIN_ARGV + ["--dp", str(dp)])
    losses = [m["loss"] for m in steps]
    check(len(losses) == 3 and all(math.isfinite(v) for v in losses),
          f"dp={dp}: losses {losses}")
    print(f"[train] dp={dp} losses {losses}, grad norms "
          f"{[m['grad_norm'] for m in steps]}")
    meter.phase(f"train dp={dp}", t0, before)
    return steps


def dp_matches(one: list, four: list) -> None:
    """dp=4 against dp=1 on the same global batch: per step, the relative
    gap of the loss and of the global gradient norm."""
    gaps = {key: max(abs(a[key] - b[key]) / abs(a[key])
                     for a, b in zip(one, four))
            for key in ("loss", "grad_norm")}
    print("[train] dp=4 vs dp=1 worst relative gap: "
          + ", ".join(f"{k} {v:.4g}" for k, v in gaps.items())
          + f" (tolerance {DP_RTOL:g})")
    for key, worst in gaps.items():
        check(worst <= DP_RTOL, f"dp=4 {key} {[m[key] for m in four]} vs "
                                f"dp=1 {[m[key] for m in one]}: relative "
                                f"gap {worst:.4g} > {DP_RTOL:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: data-parallel training on four chips only")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {ROOT}: run it from a "
              "checkout of the repo", file=sys.stderr)
        return 1
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX sees {len(devices)} "
              f"{dev.platform} device(s)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = pathlib.Path(enable_compile_cache())
    n_cached = sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0
    print(f"[chip_smoke] smoke run, not a benchmark: device_kind "
          f"{dev.device_kind!r}, {len(devices)} device(s); compile cache "
          f"{cache} holds {n_cached} entries")
    meter = CompileMeter()
    try:
        if args.chips == 4:
            dp_matches(train_steps(meter, dp=1), train_steps(meter, dp=4))
        else:
            eng, prompts, toks = serve_student(meter, packed=False)
            fused_vs_gather(meter, eng, prompts, toks)
            del eng
            serve_student(meter, packed=True)
            gc.collect()         # engines hold their weights in cycles
            train_steps(meter, dp=1)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
