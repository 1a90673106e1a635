"""Continuous-batching serving engine for BitDistill students (and FP
baselines).

Serves the paper's inference story on TPU terms: the QAT student is converted
to 2-bit-packed ternary weights (core.bitlinear.convert_linear_params_fp_to_
packed -> the w2a8 kernel path), cutting weight HBM traffic 8x vs bf16 in the
bandwidth-bound decode loop — the TPU analogue of the paper's 2.65x CPU
speedup / 10x memory saving.  That bandwidth win only materializes when the
decode batch stays full, which is what continuous batching is for.

Architecture (request lifecycle in serving/api.py, slot bookkeeping in
serving/scheduler.py):

  * ``Engine.submit()`` enqueues a :class:`GenerationRequest`; ``step()``
    admits waiting requests into free decode slots and runs ONE jitted step
    over the whole slot batch; ``stream()`` iterates steps and yields
    :class:`StepOutput` tokens as they are produced; ``generate()`` is the
    legacy blocking wrapper.
  * one preallocated cache of shape [slots, max_len]; per-row int32 cache
    indices let rows sit at different prompt/generation depths in the same
    step, so finished rows are evicted and new requests admitted without
    draining the batch.
  * **chunked, interleaved prefill** (Sarathi-style piggybacking): admission
    assigns a slot without prefilling; each ``step()`` then advances up to
    ``ServeConfig.prefill_chunk`` prompt tokens for every prefilling slot
    *and* one decode token for every decoding slot in one fused jitted step
    (chunk lengths are bucketed to powers of two to bound recompiles).  A
    slot whose chunk exhausts its prompt emits its first sampled token from
    that chunk's last-position logits.  ``prefill_chunk=0`` keeps the
    stop-the-world whole-prompt semantics — a sequential scan of decode
    steps over the full prompt, the retired admission prefill's behavior —
    as the parity and latency baseline.  The old batch-of-one prefill scan
    (``_prefill_impl``) and its prefix-KV seeding gather
    (``_seed_prefix_impl``) are retired.
  * per-request sampling: temperature / top-p / PRNG-seed vectors ride along
    the fused step; a row's PRNG key advances only when it actually consumes
    a sample (decode rows and prompt-exhausting chunks), so the per-request
    stream is identical whether the prompt prefilled in one chunk or many.
    ``max_tokens`` counts generated tokens (the first prefill-sampled token
    included), EOS stops unless ``ignore_eos``.

KV-cache layout is selectable: ``ServeConfig(paged=True)`` (the default for
attention-only models) replaces the per-slot contiguous [slots, max_len]
regions with one block pool per layer [num_kv_blocks, Hkv, block_size, Dh]
plus per-slot block tables (serving/paged.py) — resident KV bytes track the
actual token footprint instead of worst-case capacity, admission waits on
blocks as well as slots, and pool exhaustion mid-flight (decode growth or a
half-prefilled chunk) preempts the slot (recompute on re-admission).
``paged=False`` keeps the contiguous path; both produce token-for-token
identical greedy outputs (tests/test_paged_kv.py).

How the paged layout is *attended* is a second knob: ``ServeConfig(
attn_impl=...)`` selects the fused Pallas kernels — kernels/paged_attention
for pure decode steps, kernels/paged_prefill for steps that carry a chunk
(both stream each row's resident blocks out of the pools with an online
softmax; KV bytes read are O(tokens resident)) — or the dense block-table
gather fallback; ``"auto"`` picks fused on TPU and gather on CPU/interpret,
and both are greedy-parity identical (tests/test_paged_kv.py,
tests/test_chunked_prefill.py).  Models whose caches have no paged layout
(SSM / hybrid / cross-attention) run the chunked step as a masked
``lax.scan`` of decode steps over the live contiguous cache — same
interleaving, sequential within the chunk.

``ServeConfig(prefix_cache=True)`` (paged only) layers the **radix prefix
cache** (serving/prefix_cache.py) on top: admission walks a block-granular
trie of previously-prefilled token prefixes, maps matched blocks into the
slot's table via ``BlockAllocator.share()``, and prefill resumes at the
covered offset — chunk attention reads the shared prefix KV directly from
the pool blocks, so there is no seeding copy.  Publication is
as-blocks-fill: every chunk publishes the blocks it completed, so identical
prompts arriving while a long prompt is mid-prefill share its progress.
Finished/preempted requests *release* their blocks to the cache, so hot
system prompts stay resident until LRU eviction reclaims them under pool
pressure; greedy outputs are token-for-token identical with the cache on or
off (tests/test_prefix_cache.py).  ``Engine.stats()`` snapshots admissions,
preemptions, per-chunk prefill work, block occupancy, prefix counters, and
time-to-first-token percentiles.

The step itself is split **plan -> launch -> commit** (``plan_step`` /
``launch_step`` / ``commit_step``; ``step()`` composes the three for the
synchronous parity baseline): planning — deadline sweep, admission, chunk
budgeting, block allocation — touches only host state, launching uses JAX
async dispatch (the jitted call returns with the token array unmaterialized),
and commit syncs the tokens and applies them to the scheduler.
``serving/async_engine.py`` drives the phases from an asyncio loop so the
host plans step N+1 while the device runs step N, and ``plan_spec`` goes one
further for steady-state decode: it launches step N+1 *before* committing
step N, feeding step N's token device-array straight back into the next
dispatch (safe because decode positions advance deterministically; an
unpredicted EOS just discards that row at commit via the plan's slot->uid
owner snapshot).  Requests can carry deadlines and be cancelled mid-flight
(``Engine.cancel`` / ``expire_deadlines``): the slot frees immediately, its
blocks release to the allocator or stay published in the prefix cache, and
the in-flight step's row for that slot is discarded at commit.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import (Dict, Iterator, List, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model
from repro.models.base import ModelConfig
from repro.serving.api import (EngineStats, FinishReason, GenerationRequest,
                               SamplingParams, StepFailure, StepOutput,
                               make_request)
from repro.serving.paged import BlockAllocator
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.sampling import guard_nonfinite, sample_batch
from repro.serving.scheduler import Scheduler, bucket_length
from repro.serving.telemetry import Clock, Histogram, MetricsRegistry
from repro.serving.tracing import COMMIT, LAUNCH, PLAN


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8               # concurrent decode slots
    max_len: int = 256               # per-slot cache capacity (prompt + gen)
    eos_id: int = 258
    pad_id: int = 256
    temperature: float = 0.0         # default SamplingParams for bare submits
    top_p: float = 1.0
    seed: int = 0                    # base for per-request PRNG derivation
    prefill_bucket_min: int = 8      # smallest whole-prompt chunk bucket
    cache_dtype: str = "float32"     # bfloat16 on real HW
    # max prompt tokens a prefilling slot advances per engine step (chunk
    # lengths bucket to powers of two up to this, bounding recompiles);
    # 0 = whole-prompt sequential-scan prefill — the retired stop-the-world
    # admission prefill's semantics, kept as the parity/latency baseline
    prefill_chunk: int = 32
    # cap on *total* chunk tokens per engine step across all slots (None =
    # per-slot prefill_chunk only): a burst of long prompts stalls past the
    # budget instead of fattening the fused step and starving decode latency
    prefill_budget: Optional[int] = None
    # -- paged KV cache (serving/paged.py) --------------------------------
    # block-pooled KV cache: True / False force it on/off; None (default)
    # auto-selects — paged for attention-only stacks, contiguous for models
    # with SSM / cross-attention caches (which have no paged layout)
    paged: Optional[bool] = None
    kv_block_size: int = 16          # tokens per KV block
    # pool size incl. the reserved trash block; None = full capacity
    # (max_batch slots at max_len depth — no admission ever waits on blocks)
    num_kv_blocks: Optional[int] = None
    # paged attention implementation: "fused" streams KV blocks through the
    # Pallas kernels (kernels/paged_attention for decode steps,
    # kernels/paged_prefill for chunk steps), "gather" materializes the
    # dense block-table window, "auto" picks fused on TPU and the gather
    # fallback elsewhere (CPU/interpret).  Requesting "fused" off-TPU runs
    # the kernels in interpret mode (correctness path, used by the parity
    # tests).  Distinct knob from ModelConfig.attn_impl ("dense"/"blocked"),
    # which selects the *forward* attention implementation.
    attn_impl: str = "auto"
    # override the model's attention KV block length (Attention.block_kv,
    # used by the blocked/flash forward impl); None keeps the config value
    block_kv: Optional[int] = None
    # -- radix prefix cache (serving/prefix_cache.py, paged only) ----------
    # share KV blocks of repeated prompt prefixes (system prompts) across
    # requests: admission maps trie-matched blocks into the slot's table and
    # prefill resumes past them; finished/preempted requests release their
    # blocks to the cache (LRU-evicted under pool pressure)
    prefix_cache: bool = False
    # cap on blocks the trie may hold (None = unbounded; eviction then
    # happens only when alloc() would starve)
    prefix_cache_blocks: Optional[int] = None
    # -- block-pool sanitizer (repro.analysis.shadow, paged only) ----------
    # mirror every block lifecycle transition (alloc/share/free/publish)
    # through an ASan-style shadow state machine and check each step's KV
    # write-set before dispatch: any protocol violation raises
    # SanitizerError at the faulting call.  Debug/CI knob — adds O(pool)
    # host work per step, keep off in production
    sanitize: bool = False
    # with sanitize: also keep a crc per written KV block (shadow pool) and
    # let Engine.check_kv_integrity() sweep resident blocks for silent
    # device-memory corruption (bit flips, the faults.py device_mem site);
    # corrupt rows recover via targeted recompute-preemption.  Reads the
    # pool back to the host per sweep — debug/CI knob like sanitize
    kv_checksums: bool = False
    # -- request journal (serving/journal.py) ------------------------------
    # write-ahead log of every request transition (submit/admit/tokens/
    # finish/cancel/shed), fsync'd per accepted submit and per committed
    # step: a SIGKILL'd process relaunches, replays the journal
    # (serving/recovery.py), and resumes every accepted request with its
    # committed tokens forced as prefix.  None = off
    journal_dir: Optional[str] = None
    journal_fsync: bool = True       # False trades the durability fsyncs away
    journal_segment_bytes: int = 1 << 20   # rotation threshold
    journal_compact_finished: int = 32     # compaction trigger at rotation

    def __post_init__(self):
        if self.prefill_bucket_min < 1:
            raise ValueError(
                f"prefill_bucket_min={self.prefill_bucket_min} must be >= 1 "
                "(bucket_length would loop forever)")
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be >= 0 "
                "(0 = whole-prompt chunks)")
        if self.kv_block_size < 1:
            raise ValueError(f"kv_block_size={self.kv_block_size} must be >= 1")
        if self.num_kv_blocks is not None and self.num_kv_blocks < 2:
            raise ValueError(
                f"num_kv_blocks={self.num_kv_blocks}: need the reserved trash "
                "block plus at least one allocatable block")
        if self.attn_impl not in ("auto", "fused", "gather"):
            raise ValueError(
                f"attn_impl={self.attn_impl!r} must be 'auto', 'fused', or "
                "'gather'")
        if self.block_kv is not None and self.block_kv < 1:
            raise ValueError(f"block_kv={self.block_kv} must be >= 1")
        if self.prefix_cache and self.paged is False:
            raise ValueError(
                "prefix_cache=True shares paged KV blocks; it requires the "
                "paged cache (ServeConfig(paged=True) or auto)")
        if self.prefix_cache_blocks is not None and self.prefix_cache_blocks < 1:
            raise ValueError(
                f"prefix_cache_blocks={self.prefix_cache_blocks} must be "
                ">= 1 or None")
        if self.prefill_budget is not None and self.prefill_budget < 1:
            raise ValueError(
                f"prefill_budget={self.prefill_budget} must be >= 1 or None "
                "(a zero budget would stall every prefill forever)")
        if self.sanitize and self.paged is False:
            raise ValueError(
                "sanitize=True shadows the paged block pool; it requires "
                "the paged cache (ServeConfig(paged=True) or auto)")
        if self.kv_checksums and not self.sanitize:
            raise ValueError(
                "kv_checksums=True stores block digests in the sanitizer "
                "shadow pool; it requires ServeConfig(sanitize=True)")
        if self.journal_segment_bytes < 1:
            raise ValueError(
                f"journal_segment_bytes={self.journal_segment_bytes} must "
                "be >= 1")

    @property
    def blocks_per_slot(self) -> int:
        """Logical blocks covering one slot's max_len positions."""
        return -(-self.max_len // self.kv_block_size)

    def pool_blocks(self) -> int:
        """Physical pool size (trash block + allocatable blocks)."""
        if self.num_kv_blocks is not None:
            return self.num_kv_blocks
        return 1 + self.max_batch * self.blocks_per_slot


@dataclasses.dataclass
class Request:
    """Legacy request type, kept for the ``generate()`` compatibility path."""
    uid: int
    prompt: List[int]
    max_tokens: int = 32
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class StepPlan:
    """Host-side plan for one fused step, produced by ``Engine.plan_step()``
    (or ``plan_spec()``) with **no device sync**: admission, deadline sweep,
    chunk planning, and block allocation all happen here, so the async loop
    can plan step N+1 while step N is still executing.

    ``events`` are terminal StepOutputs already emitted during planning
    (admission rejections, deadline expiries) — their callbacks have fired;
    ``commit_step`` only prepends them to its return.  ``owners`` snapshots
    slot -> uid at plan time so a slot freed mid-flight (cancel / deadline)
    has its in-flight token discarded at commit instead of being credited to
    the slot's next occupant.  ``stalled`` lists mid-prefill slots past the
    step's ``prefill_budget``: they ride the fused step as emit-less pad rows
    (their stale KV write is overwritten bit-identically by the real chunk
    before anything attends it) and are skipped at commit.  ``spec`` marks a
    speculative decode plan: launch feeds the *device array* of the previous
    step's sampled tokens instead of the host-synced ``_tokens``."""
    events: List[StepOutput]
    active: List[int]
    owners: Dict[int, int]
    chunks: Dict[int, int]
    stalled: List[int]
    positions: np.ndarray          # per-slot write positions at plan time
    spec: bool = False


def step_kind(plan: StepPlan) -> str:
    """Which program a plan launches: ``chunk`` when a slot prefills (or
    rides along budget-stalled), ``decode`` otherwise, ``idle`` when no
    slot is active and nothing is launched."""
    if not plan.active:
        return "idle"
    return "chunk" if plan.chunks or plan.stalled else "decode"


class StepWork(NamedTuple):
    """What one launched step computes, counted on the host from its plan
    (no device read).  ``positions_run``: positions every linear layer
    computes (``max_batch x chunk bucket``, or ``max_batch`` for decode);
    ``positions_useful``: those that carry a request (a chunk row's chunk,
    a decoding row's one token; budget-stalled pad rows carry none).
    ``kv_blocks_walked``: the block table handed to the attention kernel,
    per KV head per layer (``max_batch x table width``); ``kv_blocks_live``:
    the blocks each active row's positions up to this step's last write
    occupy; ``kv_blocks_scored``: the blocks the kernel fetches per KV head
    per layer — in a fused chunk step the prefill kernel's rule
    (``kernels/paged_prefill/ops.fetched_blocks``: each row's resident real
    blocks, scored, plus the real blocks its chunk is spliced into; an idle
    row none), otherwise the whole table (the decode kernel and the gather
    path score every column).  The block counts are 0 on the contiguous
    cache."""
    kind: str
    rows: int
    positions_run: int
    positions_useful: int
    kv_blocks_walked: int
    kv_blocks_live: int
    kv_blocks_scored: int


@dataclasses.dataclass
class InflightStep:
    """A dispatched-but-uncommitted step: the plan it ran, the un-synced
    device array of sampled tokens (``None`` when no slot was active), and
    the wall-clock instant dispatch returned (for the step-gap metric).
    ``step`` is the id it commits under (a speculative launch runs one
    ahead of the commits), ``work`` its :class:`StepWork`, added to the
    stats only once it commits.  ``write_blocks`` is the step's physical
    KV write-set, captured by the sanitizer at launch (table state at
    dispatch time) so the ``kv_checksums`` commit can digest exactly the
    blocks this step wrote."""
    plan: StepPlan
    tok: Optional[jax.Array]
    launched_at: float = 0.0
    write_blocks: Optional[List[int]] = None
    step: int = 0
    work: Optional[StepWork] = None


class Engine:
    """Continuous-batching facade: ``submit() / step() / stream()`` plus the
    blocking ``generate()`` compatibility wrapper."""

    def __init__(self, cfg: ModelConfig, params,
                 scfg: Optional[ServeConfig] = None,
                 clock: Optional[Clock] = None):
        # the engine's sole timestamp source (serving/telemetry.py): every
        # former time.perf_counter() site reads engine.clock.now(), so the
        # tracer shares the latency metrics' timeline and tests can swap in
        # a FakeClock before the first submit
        self.clock = clock if clock is not None else Clock()
        self.scfg = scfg if scfg is not None else ServeConfig()
        if self.scfg.block_kv is not None:
            cfg = cfg.replace(block_kv=self.scfg.block_kv)
        self.cfg, self.params = cfg, params
        self.model = build_model(cfg)
        attn_only = all(s.mixer == "attn" for s in cfg.resolved_pattern())
        if self.scfg.paged and not attn_only:
            raise ValueError(
                "paged KV cache supports attention-only decoder stacks; "
                f"config {cfg.name!r} has mixers "
                f"{[s.mixer for s in cfg.resolved_pattern()]} — pass "
                "ServeConfig(paged=False) for the contiguous cache")
        self.paged = attn_only if self.scfg.paged is None else self.scfg.paged
        impl = self.scfg.attn_impl
        if impl == "auto":
            # the fused kernels target TPU; elsewhere (CPU CI) the gather
            # fallback is both faster and what interpret mode exists to test
            impl = ("fused" if self.paged and jax.default_backend() == "tpu"
                    else "gather")
        if impl == "fused" and not self.paged:
            raise ValueError(
                "attn_impl='fused' selects the paged-pool kernels; they "
                "require the paged KV cache (ServeConfig(paged=True))")
        self.attn_impl = impl
        self.allocator = (BlockAllocator(self.scfg.pool_blocks(),
                                         self.scfg.kv_block_size)
                          if self.paged else None)
        self.prefix_cache: Optional[RadixPrefixCache] = None
        if self.scfg.prefix_cache:
            if not self.paged:
                raise ValueError(
                    "prefix_cache=True requires the paged KV cache; this "
                    "model resolved to the contiguous layout — pass "
                    "ServeConfig(paged=True) for an attention-only stack")
            self.prefix_cache = RadixPrefixCache(
                self.allocator, self.scfg.prefix_cache_blocks)
            # alloc() LRU-evicts cached-but-unreferenced prefix blocks
            # before reporting starvation to admission/growth
            self.allocator.reclaim = self.prefix_cache.evict
        self.sched = Scheduler(self.scfg.max_batch, self.scfg.max_len,
                               self.scfg.eos_id, self.scfg.prefill_bucket_min,
                               allocator=self.allocator,
                               prefix_cache=self.prefix_cache,
                               prefill_chunk=self.scfg.prefill_chunk,
                               prefill_budget=self.scfg.prefill_budget)
        # ASan-style shadow of the block pool (repro.analysis.shadow): the
        # allocator reports every refcount transition, the scheduler / prefix
        # cache declare what each reference means, and launch/commit check
        # write-sets and cross-verify the mirror.  Lazy import keeps the
        # serving stack free of the analysis package unless asked for.
        self.shadow = None
        if self.scfg.sanitize:
            if not self.paged:
                raise ValueError(
                    "sanitize=True shadows the paged block pool; this model "
                    "resolved to the contiguous layout — pass "
                    "ServeConfig(paged=True) for an attention-only stack")
            from repro.analysis.shadow import ShadowBlockPool
            self.shadow = ShadowBlockPool(self.allocator.num_blocks,
                                          self.allocator.block_size,
                                          checksums=self.scfg.kv_checksums)
            self.allocator.observer = self.shadow
            self.sched.shadow = self.shadow
            if self.prefix_cache is not None:
                self.prefix_cache.shadow = self.shadow
        # request write-ahead log (serving/journal.py): accepted submits and
        # committed tokens are fsync'd before they are observable, so a
        # killed process recovers them (serving/recovery.py).  Opening
        # always starts a fresh segment — a crashed predecessor's torn tail
        # is never buried mid-file.
        self.journal = None
        if self.scfg.journal_dir:
            from .journal import Journal
            self.journal = Journal(
                self.scfg.journal_dir,
                segment_bytes=self.scfg.journal_segment_bytes,
                fsync=self.scfg.journal_fsync,
                compact_min_finished=self.scfg.journal_compact_finished)
        # the jitted step impls, built from one registry so tooling (the
        # retrace watchdog, tests) can rebuild them with wrappers: attr ->
        # (python impl, donate_argnums).  Donating the cache (and key)
        # buffers lets XLA update them in place instead of copying the whole
        # cache (contiguous [slots, max_len] regions or the paged block
        # pool) every step (no-op on backends without donation, e.g. CPU).
        # _chunk is the fused chunk step, retraced per (chunk bucket, table
        # width): prefill_chunk > 0 on paged models runs chunk attention
        # (kernels/paged_prefill or the gather fallback); contiguous/SSM
        # models — and prefill_chunk == 0, the legacy stop-the-world
        # whole-prompt baseline — run a sequential scan of decode steps
        # (_chunk_scan).
        self._jit_specs = {
            "_decode": (self._decode_impl, (2, 4)),
            "_chunk_scan": (self._chunk_scan_paged_impl if self.paged
                            else self._chunk_scan_impl, (2, 6)),
        }
        if self.paged:
            self._jit_specs["_chunk"] = (self._chunk_step_impl, (2, 6))
        self._chunk = None
        for attr, (impl, donate) in self._jit_specs.items():
            setattr(self, attr, jax.jit(impl, donate_argnums=donate))
        # prefill work counters (Engine.stats()): positions run through
        # chunk steps (counted per chunk, not per admission) vs positions
        # skipped via shared blocks, and how many chunks it took
        self._prefill_positions = 0
        self._prefill_skipped = 0
        self._prefill_chunks = 0
        # step-work counters (StepWork, summed per committed step): useful
        # against computed positions over chunk steps, live and scored
        # against walked KV blocks over steps of both kinds
        self._chunk_positions_run = 0
        self._chunk_positions_useful = 0
        self._kv_blocks_walked = 0
        self._kv_blocks_live = 0
        self._kv_blocks_scored = 0
        self._uid_counter = 0
        self._requests: Dict[int, GenerationRequest] = {}   # uid -> in flight
        self._submit_ts: Dict[int, float] = {}   # uid -> submit wall time
        # latency series are fixed-memory log-bucketed histograms
        # (serving/telemetry.py) — O(1) per observe, snapshot-cheap mid-run;
        # the attr names are load-bearing (supervisor._carry_stats copies
        # these objects across restarts, so series are cumulative)
        self._ttft_ms = Histogram()              # submit -> first token
        self._queue_wait_ms = Histogram()        # submit -> admission
        self._e2e_ms = Histogram()               # submit -> finish
        # host dispatch-gap accounting (EngineStats.step_gap_ms): wall time
        # from a step's device sync to the next step's dispatch return; a
        # step launched *before* the previous sync (the async loop's
        # speculative launches) counts as overlapped, gap 0 by construction
        self._step_gap_ms = Histogram()
        self._last_sync: Optional[float] = None
        self._requests_submitted = 0
        self._steps_committed = 0
        self._steps_overlapped = 0
        self._tokens_generated = 0
        self._cancellations = 0
        self._deadline_expirations = 0
        # robustness counters (EngineStats; bumped here and by the serving
        # supervisor) and the fault-injection hook: when set (repro.serving.
        # faults.FaultPlan.engine_hook), it is called at the plan / launch /
        # commit seams and may raise an injected fault, sleep, or corrupt the
        # commit's synced tokens — always *before* any scheduler mutation, so
        # a failed step is side-effect-free to replay
        self.fault_hook = None
        self._step_failures = 0
        self._step_retries = 0
        self._quarantines = 0
        self._engine_restarts = 0
        self._load_sheds = 0
        self._hung_steps = 0
        self._degrade_tier = 0
        self._kv_corruptions = 0
        self._recovery_ms = Histogram()
        # opt-in telemetry sinks, None by default so the hot path pays one
        # attribute check when they are off: a serving/tracing.Tracer
        # recording span timelines, and a telemetry.FlightRecorder ring the
        # supervisor dumps on recovery actions (attached via its factory)
        self.tracer = None
        self.recorder = None
        # live decode state, allocated lazily on first admission; idle rows
        # hold pad_id so their (discarded) compute never depends on a dead
        # request's last token
        self._cache = None
        self._tokens = np.full((self.scfg.max_batch,), self.scfg.pad_id,
                               np.int32)
        self._keys = None                             # uint32 [slots, 2]
        # shape of the most recent fused step (active slots, per-slot
        # positions, bucketed table width, chunk plan), set at launch before
        # the tracer's launch_span fires: the serving benchmark (bench/)
        # reads it there to count each step's work from its shape
        self.last_decode: Optional[Dict] = None
        self._build_metrics()

    def _build_metrics(self) -> None:
        """(Re)build the metrics registry over the engine's live state.

        Histograms are registered as owned objects; the step/robustness
        counters export through render-time callbacks so the hot path keeps
        plain integer increments.  Called again by the supervisor after
        ``_carry_stats`` re-homes the histogram objects on a restarted
        engine, rebinding every callback to the new instance.  The metric
        names here are the canonical catalog (README "Observability") and
        map 1:1 onto :class:`EngineStats` fields."""
        reg = MetricsRegistry()
        for name, hist, help_ in (
            ("serving_ttft_ms", self._ttft_ms,
             "submit -> first token latency (EngineStats.ttft_ms)"),
            ("serving_queue_wait_ms", self._queue_wait_ms,
             "submit -> admission wait (EngineStats.queue_wait_ms)"),
            ("serving_e2e_latency_ms", self._e2e_ms,
             "submit -> finish latency (EngineStats.e2e_latency_ms)"),
            ("serving_step_gap_ms", self._step_gap_ms,
             "device sync -> next dispatch gap (EngineStats.step_gap_ms)"),
            ("serving_recovery_ms", self._recovery_ms,
             "failure -> healthy commit (EngineStats.recovery_ms)"),
        ):
            reg.register(name, hist, help_)
        for name, kind, fn, help_ in (
            ("serving_requests_submitted_total", "counter",
             lambda: self._requests_submitted,
             "requests accepted by submit_request "
             "(EngineStats.requests_submitted)"),
            ("serving_admissions_total", "counter",
             lambda: self.sched.admissions,
             "requests admitted to slots (EngineStats.admissions)"),
            ("serving_preemptions_total", "counter",
             lambda: self.sched.preemptions,
             "slots preempted for recompute (EngineStats.preemptions)"),
            ("serving_steps_committed_total", "counter",
             lambda: self._steps_committed,
             "fused steps committed (EngineStats.steps_committed)"),
            ("serving_steps_overlapped_total", "counter",
             lambda: self._steps_overlapped,
             "steps launched before the previous sync "
             "(EngineStats.steps_overlapped)"),
            ("serving_tokens_generated_total", "counter",
             lambda: self._tokens_generated,
             "tokens emitted to requests (EngineStats.tokens_generated)"),
            ("serving_prefill_positions_total", "counter",
             lambda: self._prefill_positions,
             "prompt positions run through chunk steps "
             "(EngineStats.prefill_positions)"),
            ("serving_prefill_positions_skipped_total", "counter",
             lambda: self._prefill_skipped,
             "prompt positions covered by shared prefix blocks "
             "(EngineStats.prefill_positions_skipped)"),
            ("serving_prefill_chunks_total", "counter",
             lambda: self._prefill_chunks,
             "prefill chunks advanced (EngineStats.prefill_chunks)"),
            ("serving_chunk_positions_run_total", "counter",
             lambda: self._chunk_positions_run,
             "positions computed by chunk steps "
             "(EngineStats.chunk_positions_run)"),
            ("serving_chunk_positions_useful_total", "counter",
             lambda: self._chunk_positions_useful,
             "chunk-step positions that carry a request "
             "(EngineStats.chunk_positions_useful)"),
            ("serving_kv_blocks_walked_total", "counter",
             lambda: self._kv_blocks_walked,
             "KV blocks of the tables handed to the attention kernels, per "
             "KV head per layer (EngineStats.kv_blocks_walked)"),
            ("serving_kv_blocks_live_total", "counter",
             lambda: self._kv_blocks_live,
             "walked KV blocks that hold active rows' data "
             "(EngineStats.kv_blocks_live)"),
            ("serving_kv_blocks_scored_total", "counter",
             lambda: self._kv_blocks_scored,
             "KV blocks the attention kernels fetch, per KV head per layer "
             "(EngineStats.kv_blocks_scored)"),
            ("serving_cancellations_total", "counter",
             lambda: self._cancellations,
             "client cancellations (EngineStats.cancellations)"),
            ("serving_deadline_expirations_total", "counter",
             lambda: self._deadline_expirations,
             "requests finished by deadline "
             "(EngineStats.deadline_expirations)"),
            ("serving_step_failures_total", "counter",
             lambda: self._step_failures,
             "step failures observed (EngineStats.step_failures)"),
            ("serving_step_retries_total", "counter",
             lambda: self._step_retries,
             "step retries attempted (EngineStats.step_retries)"),
            ("serving_quarantines_total", "counter",
             lambda: self._quarantines,
             "requests quarantined with FinishReason.ERROR "
             "(EngineStats.quarantines)"),
            ("serving_engine_restarts_total", "counter",
             lambda: self._engine_restarts,
             "supervisor snapshot-restores (EngineStats.engine_restarts)"),
            ("serving_load_sheds_total", "counter",
             lambda: self._load_sheds,
             "queued requests shed under pressure "
             "(EngineStats.load_sheds)"),
            ("serving_hung_steps_total", "counter",
             lambda: self._hung_steps,
             "watchdog-flagged slow commits (EngineStats.hung_steps)"),
            ("serving_queue_depth", "gauge",
             lambda: len(self.sched.waiting),
             "requests waiting for a slot (EngineStats.queue_depth)"),
            ("serving_active_slots", "gauge",
             lambda: len(self.sched.active_slots()),
             "slots currently decoding or prefilling"),
            ("serving_degrade_tier", "gauge",
             lambda: self._degrade_tier,
             "graceful-degradation tier (EngineStats.degrade_tier)"),
            ("serving_kv_blocks_free", "gauge",
             lambda: (self.allocator.available()
                      if self.allocator is not None else 0),
             "allocatable KV blocks (EngineStats.blocks_free)"),
            ("serving_kv_blocks_in_use", "gauge",
             lambda: (self.allocator.blocks_in_use()
                      if self.allocator is not None else 0),
             "referenced KV blocks (EngineStats.blocks_in_use)"),
        ):
            reg.register_callback(name, kind, fn, help_)
        self.metrics = reg

    # -- jitted cores -----------------------------------------------------------

    def _decode_impl(self, params, tokens, cache, index, keys, temps, top_ps,
                     block_tables=None):
        """One pure-decode step: tokens [B], per-row cache index [B],
        per-row PRNG keys [B, 2] and sampling params [B].  ``block_tables``
        (int32 [B, L]) selects the paged-pool cache layout; ``self.attn_impl``
        (resolved once at construction) picks fused-kernel vs gather paged
        attention."""
        logits, cache = self.model.decode_step(params, tokens, cache, index,
                                               block_tables=block_tables,
                                               attn_impl=self.attn_impl)
        split = jax.vmap(jax.random.split)(keys)       # [B, 2, 2]
        new_keys, subs = split[:, 0], split[:, 1]
        nxt = guard_nonfinite(sample_batch(subs, logits, temps, top_ps),
                              logits)
        return nxt, cache, new_keys

    def _chunk_step_impl(self, params, tokens, cache, start, lens, emit, keys,
                         temps, top_ps, block_tables):
        """One fused chunk step over the paged pools: tokens [B, T] hold each
        row's chunk (prefilling rows: the next ``lens`` prompt tokens;
        decoding rows: ``lens == 1``, the last sampled token; idle rows: pad),
        written at positions ``start + j`` and attending ``<= start + j``
        (kernels/paged_prefill, or the chunk-gather fallback).  Samples from
        every row's last valid position; a row's PRNG key advances only where
        ``emit`` is set (rows that actually consume the sample), so chunked
        and whole-prompt prefill produce identical per-request key streams."""
        logits, cache = self.model.decode_chunk(
            params, tokens, cache, start, lens, block_tables,
            attn_impl=self.attn_impl)
        last = jnp.take_along_axis(logits, (lens - 1)[:, None, None],
                                   axis=1)[:, 0]
        split = jax.vmap(jax.random.split)(keys)       # [B, 2, 2]
        new_keys = jnp.where(emit[:, None], split[:, 0], keys)
        nxt = guard_nonfinite(sample_batch(split[:, 1], last, temps, top_ps),
                              last)
        return nxt, cache, new_keys

    def _chunk_scan_impl(self, params, tokens, cache, start, lens, emit, keys,
                         temps, top_ps):
        """Chunk-step fallback for caches with no paged layout (SSM / hybrid
        / cross): a ``lax.scan`` of decode steps over the chunk positions on
        the live contiguous cache, with per-row masking — row ``b``'s cache
        update at scan index ``j`` sticks iff ``j < lens[b]`` (pad positions
        and already-decoded rows are reverted, keeping recurrent SSM states
        exact), and its logits are captured at ``j == lens[b] - 1``.  Same
        interleaving contract as ``_chunk_step_impl``, sequential within the
        chunk."""
        b, slen = tokens.shape

        def step(carry, j):
            cache, last = carry
            logits, new_cache = self.model.decode_step(params, tokens[:, j],
                                                       cache, start + j)
            keep = j < lens                            # [B]

            def sel(n, o):
                k = keep.reshape((1, -1) + (1,) * (n.ndim - 2))
                return jnp.where(k, n, o)

            cache = jax.tree_util.tree_map(sel, new_cache, cache)
            last = jnp.where((j == lens - 1)[:, None], logits, last)
            return (cache, last), None

        init = (cache, jnp.zeros((b, self.cfg.padded_vocab),
                                 logits_dtype(self.cfg)))
        (cache, last), _ = jax.lax.scan(step, init, jnp.arange(slen))
        split = jax.vmap(jax.random.split)(keys)
        new_keys = jnp.where(emit[:, None], split[:, 0], keys)
        nxt = guard_nonfinite(sample_batch(split[:, 1], last, temps, top_ps),
                              last)
        return nxt, cache, new_keys

    def _chunk_scan_paged_impl(self, params, tokens, cache, start, lens, emit,
                               keys, temps, top_ps, block_tables):
        """Sequential chunk scan over the *paged* pools — the
        ``prefill_chunk=0`` stop-the-world baseline (the retired
        token-at-a-time admission prefill's semantics, batched over slots).

        The shared pools cannot be per-row reverted like the contiguous
        cache, so pad steps are made idempotent instead of masked: row ``b``
        at scan index ``j`` replays position ``start + min(j, lens - 1)``
        with its own token once ``j >= lens`` — the KV projection depends
        only on (token, position), so the rewrite stores bit-identical
        values, and the row's logits were already captured at
        ``j == lens - 1``.  Sound for attention KV only; paged stacks are
        attention-only by construction."""
        b, slen = tokens.shape

        def step(carry, j):
            cache, last = carry
            jj = jnp.minimum(j, lens - 1)              # [B]
            tok = jnp.take_along_axis(tokens, jj[:, None], axis=1)[:, 0]
            logits, cache = self.model.decode_step(
                params, tok, cache, start + jj, block_tables=block_tables,
                attn_impl=self.attn_impl)
            last = jnp.where((j == lens - 1)[:, None], logits, last)
            return (cache, last), None

        init = (cache, jnp.zeros((b, self.cfg.padded_vocab),
                                 logits_dtype(self.cfg)))
        (cache, last), _ = jax.lax.scan(step, init, jnp.arange(slen))
        split = jax.vmap(jax.random.split)(keys)
        new_keys = jnp.where(emit[:, None], split[:, 0], keys)
        nxt = guard_nonfinite(sample_batch(split[:, 1], last, temps, top_ps),
                              last)
        return nxt, cache, new_keys

    # -- request lifecycle --------------------------------------------------------

    def submit(self, prompt: Sequence[int],
               params: Optional[SamplingParams] = None,
               uid: Optional[int] = None,
               on_token=None,
               deadline_s: Optional[float] = None) -> GenerationRequest:
        """Enqueue a prompt; returns the live GenerationRequest handle.
        ``deadline_s`` (relative seconds from now) arms a per-request
        deadline: once it passes, the next plan/step boundary finishes the
        request with ``FinishReason.DEADLINE`` wherever it is — queued,
        mid-prefill, or mid-decode — keeping any tokens generated so far."""
        if uid is None:
            uid = self._uid_counter
        self._uid_counter = max(self._uid_counter, uid) + 1
        if params is None:
            params = SamplingParams(temperature=self.scfg.temperature,
                                    top_p=self.scfg.top_p)
        deadline = (None if deadline_s is None
                    else self.clock.now() + deadline_s)
        req = make_request(prompt, uid, params, on_token, deadline=deadline)
        return self.submit_request(req)

    def submit_request(self, req: GenerationRequest) -> GenerationRequest:
        if req.uid in self._requests:
            raise ValueError(
                f"uid {req.uid} already belongs to an in-flight request; "
                "reusing it would orphan that request's callback and finish "
                "bookkeeping")
        now = self.clock.now()
        self._requests[req.uid] = req
        self._submit_ts[req.uid] = now
        self._requests_submitted += 1
        if self.tracer is not None:
            # idempotent per uid: supervisor restarts re-submit salvaged
            # requests without opening (or counting) a second root span
            self.tracer.request_submit(req.uid, now)
        if self.journal is not None:
            # durable before the caller sees the uid: an acked submit is
            # never lost to a crash (replay treats re-submits as first-wins,
            # so supervisor restarts / recovery re-admissions are free)
            self.journal.log_submit(req, now_mono=now)
        self.sched.submit(req)
        return req

    def has_pending(self) -> bool:
        return self.sched.has_work()

    def step(self) -> List[StepOutput]:
        """Admit waiting requests, then run one fused step over the slot
        batch: every prefilling slot advances up to ``prefill_chunk`` prompt
        tokens and every decoding slot one token (Sarathi-style
        interleaving).  Returns the StepOutputs produced (rejections and
        deadline expiries, then one token per slot that completed its prompt
        or decoded).  Internally plan -> launch -> commit; the async loop
        (serving/async_engine.py) calls those phases separately so the host
        plans step N+1 while the device runs step N."""
        return self.commit_step(self.launch_step(self.plan_step()))

    # -- plan / launch / commit ------------------------------------------------

    def plan_step(self) -> StepPlan:
        """Plan one fused step on the host, with no device sync: sweep
        expired deadlines, admit waiting requests (keys set, prefix-skip
        accounted, queue-wait recorded), plan this step's chunks (which may
        preempt starved slots), and snapshot active slots / owners /
        positions.  Rejection and deadline marker events are finalized here
        (callbacks fire at plan time) and carried in ``plan.events``."""
        with jax.profiler.TraceAnnotation(
                PLAN, step=self._steps_committed) as ann:
            t_plan = self.clock.now()
            if self.fault_hook is not None:
                # fires before any side effect: a raised plan fault leaves
                # the scheduler untouched and the supervisor simply replans
                self.fault_hook("plan", {})
            self.last_decode = None        # stays None if no slot runs
            events = self.expire_deadlines()
            admitted, rejected = self.sched.admit()
            self._finalize_outputs(rejected)
            events.extend(rejected)
            if admitted:
                self._ensure_state()
                now = self.clock.now()
                if self.journal is not None:
                    for _, req in admitted:
                        # advisory (recovery re-admits from scratch anyway):
                        # buffered until the step's commit fsync
                        self.journal.log_admit(req.uid)
                for slot, req in admitted:
                    self._keys = self._keys.at[slot].set(
                        self._request_key(req))
                    # positions covered by trie-shared blocks skip prefill;
                    # on a preemption resume this counts the re-matched
                    # progress too
                    self._prefill_skipped += int(self.sched.prefix_lens[slot])
                    t0 = self._submit_ts.get(req.uid)
                    if t0 is not None:
                        self._queue_wait_ms.observe((now - t0) * 1e3)
                    if self.tracer is not None:
                        self.tracer.request_admitted(req.uid, now)
            # plan this step's chunks (may preempt half-prefilled slots whose
            # growth starves; may stall slots past the prefill budget)
            chunks = self.sched.next_chunks()
            active = self.sched.active_slots()
            stalled = [s for s in active
                       if self.sched.pending[s] and s not in chunks]
            owners = {s: self.sched.slots[s].uid for s in active}
            if self.tracer is not None:
                self.tracer.plan_span(t_plan, self.clock.now(),
                                      self._steps_committed, len(active),
                                      len(chunks))
            plan = StepPlan(events=events, active=active, owners=owners,
                            chunks=chunks, stalled=stalled,
                            positions=self.sched.positions.astype(np.int32,
                                                                  copy=True))
            ann.set_metadata(kind=step_kind(plan))
        return plan

    def plan_spec(self, inflight: InflightStep) -> Optional[StepPlan]:
        """Plan step N+1 *speculatively* while step N (``inflight``) is still
        on the device, or return None when only a normal post-commit plan is
        safe.  Speculation requires a pure-decode in-flight step whose slots
        all provably survive its commit: same active set and owners, no row
        finishing deterministically (max_tokens / cache capacity) at commit,
        and every next-position block allocatable (``pregrow_decode``).  An
        EOS finish is *allowed* — the speculative step's row is discarded at
        commit via the owner check, and its stale KV write lands in a block
        nothing attends before it is overwritten.  Declined when admission
        could run instead (waiting requests + a free slot): filling a slot
        beats overlapping one step."""
        step = self._steps_committed + 1     # launched before N commits
        with jax.profiler.TraceAnnotation(PLAN, step=step, kind="decode"):
            t_plan = self.clock.now()
            plan = inflight.plan
            if inflight.tok is None or plan.chunks or plan.stalled:
                return None                # only pure-decode steps speculate
            sc = self.sched
            active = sc.active_slots()
            if not active or active != plan.active:
                return None                # a cancel/deadline freed a slot
            if sc.waiting and any(r is None for r in sc.slots):
                return None                # admission possible: plan it
            for slot in active:
                req = sc.slots[slot]
                if req is None or req.uid != plan.owners.get(slot):
                    return None
                # the in-flight step appends one token at commit; a row
                # that deterministically finishes there frees its slot —
                # plan for real
                if req.num_generated + 1 >= req.params.max_tokens:
                    return None
                if int(sc.positions[slot]) + 1 > sc.max_len - 1:
                    return None            # capacity finish at commit
                if not sc.pregrow_decode(slot):
                    return None            # pool starved: let commit preempt
            positions = sc.positions.astype(np.int32, copy=True)
            for slot in active:
                positions[slot] += 1       # where N+1 writes, post-commit-N
            if self.tracer is not None:
                self.tracer.plan_span(t_plan, self.clock.now(), step,
                                      len(active), 0, spec=True)
            return StepPlan(events=[], active=list(active),
                            owners=dict(plan.owners), chunks={}, stalled=[],
                            positions=positions, spec=True)

    def launch_step(self, plan: StepPlan,
                    feed: Optional[InflightStep] = None) -> InflightStep:
        """Dispatch the planned fused step without syncing its outputs.  JAX
        async dispatch returns as soon as the computation is enqueued, so the
        returned :class:`InflightStep` holds an unmaterialized token array —
        the host is free to plan (and with ``plan_spec``, even launch) the
        next step while the device executes.  A speculative plan feeds
        ``feed.tok`` — the previous step's *device* tokens — instead of the
        host-synced ``self._tokens``."""
        step = self._steps_committed + int(plan.spec)
        with jax.profiler.TraceAnnotation(LAUNCH, step=step,
                                          kind=step_kind(plan)):
            t_launch = self.clock.now()
            if not plan.active:
                return InflightStep(plan=plan, tok=None, launched_at=t_launch,
                                    step=step)
            if self.fault_hook is not None:
                # fires before dispatch: a raised launch fault (or injected
                # slow/hung step) leaves device state untouched — the same
                # plan relaunches verbatim
                self.fault_hook("launch", {"plan": plan})
            self._ensure_state()
            write_blocks = None
            if self.shadow is not None:
                write_blocks = self._sanitize_writes(plan)
            if plan.chunks or plan.stalled:
                tok, work = self._launch_chunk(plan)
            else:
                tok, work = self._launch_decode(plan, feed)
            launched_at = self.clock.now()
            if self.tracer is not None:
                self.tracer.launch_span(t_launch, launched_at, step,
                                        plan.spec)
            return InflightStep(plan=plan, tok=tok, launched_at=launched_at,
                                write_blocks=write_blocks, step=step,
                                work=work)

    def commit_step(self, inflight: InflightStep,
                    tok_np: Optional[np.ndarray] = None) -> List[StepOutput]:
        """Sync the in-flight step's tokens off the device and apply them to
        the scheduler: ``advance_prefill`` for chunked slots, ``record`` for
        every slot that produced a token.  Rows whose slot changed owner
        since the plan (cancel / deadline / EOS-finish under speculation) are
        discarded; budget-stalled rows are skipped.  ``tok_np`` lets the
        async loop pass tokens it already materialized off-thread.  Returns
        the plan's marker events followed by this step's outputs."""
        with jax.profiler.TraceAnnotation(COMMIT, step=self._steps_committed,
                                          kind=step_kind(inflight.plan)):
            plan = inflight.plan
            sc = self.sched
            outs: List[StepOutput] = []
            if inflight.tok is not None:
                if tok_np is None:
                    # the step's one budgeted device sync
                    tok_np = np.asarray(inflight.tok)  # lint: allow(host-sync)
                if self.fault_hook is not None:
                    # fires after the sync but before validation/mutation;
                    # may raise an injected device fault or corrupt token rows
                    # (the NaN-logits simulation — replaced array read back
                    # from ctx)
                    ctx = {"plan": plan, "tok": tok_np}
                    self.fault_hook("commit", ctx)
                    tok_np = ctx["tok"]
                # validate *before* any scheduler/request mutation: a failed
                # step must be side-effect-free so the supervisor can
                # relaunch the same plan (KV rewrites are (token,
                # position)-determined, hence bit-identical on replay)
                self._validate_tokens(plan, tok_np)
                now = self.clock.now()
                step_id = self._steps_committed
                self._steps_committed += 1
                if self._last_sync is not None:
                    gap = inflight.launched_at - self._last_sync
                    if gap <= 0.0:
                        self._steps_overlapped += 1
                    self._step_gap_ms.observe(max(0.0, gap) * 1e3)
                self._last_sync = now
                for slot in plan.active:
                    req = sc.slots[slot]
                    if req is None or req.uid != plan.owners.get(slot):
                        continue       # slot freed mid-flight: discard token
                    n = plan.chunks.get(slot)
                    if n is not None:
                        if not sc.advance_prefill(slot, n):
                            continue   # still prefilling: no token this step
                    elif slot in plan.stalled:
                        continue       # budget-stalled: emit-less pad row
                    self._tokens[slot] = int(tok_np[slot])
                    outs.append(sc.record(slot, int(tok_np[slot])))
                self._prefill_positions += sum(plan.chunks.values())
                self._prefill_chunks += len(plan.chunks)
                if (self.shadow is not None
                        and self.shadow.checksums_enabled
                        and inflight.write_blocks):
                    # refresh the content digest of every block this step
                    # wrote (captured at launch); blocks freed by this commit
                    # are skipped inside note_checksum
                    for b, d in self._kv_block_digests(
                            inflight.write_blocks).items():
                        self.shadow.note_checksum(b, d)
                w = inflight.work
                if w.kind == "chunk":
                    self._chunk_positions_run += w.positions_run
                    self._chunk_positions_useful += w.positions_useful
                self._kv_blocks_walked += w.kv_blocks_walked
                self._kv_blocks_live += w.kv_blocks_live
                self._kv_blocks_scored += w.kv_blocks_scored
                if self.tracer is not None:
                    # the commit span covers the scheduler application.  One
                    # chunk span per planned chunk keeps
                    # counts['prefill_chunk'] == EngineStats.prefill_chunks
                    # (both count plan.chunks of committed steps, owner-valid
                    # or not); commit spans and step_work records mirror
                    # _steps_committed exactly.
                    for slot, n in plan.chunks.items():
                        self.tracer.prefill_chunk(plan.owners.get(slot, -1),
                                                  inflight.launched_at, now,
                                                  n)
                    self.tracer.step_work(step_id, w.kind,
                                          inflight.launched_at, w.rows,
                                          w.positions_run, w.positions_useful,
                                          w.kv_blocks_walked,
                                          w.kv_blocks_live)
                    self.tracer.step_scored(step_id, w.kind,
                                            inflight.launched_at,
                                            w.kv_blocks_scored)
                    self.tracer.commit_span(now, self.clock.now(), step_id,
                                            len(outs), len(plan.chunks))
                if self.recorder is not None:
                    self.recorder.record("commit", step=step_id,
                                         active=len(plan.active),
                                         chunks=len(plan.chunks),
                                         outputs=len(outs), spec=plan.spec)
            # any slot freed this step (finish, cancel, or paged preemption)
            # must decode the pad token while idle, not the dead request's
            # last token
            for slot, req in enumerate(sc.slots):
                if req is None:
                    self._tokens[slot] = self.scfg.pad_id
            if self.shadow is not None:
                # cross-check the mirror against the live allocator every
                # step, and assert the drained invariant (no OWNED/SHARED
                # blocks) the moment no work remains
                self.shadow.verify(self.allocator)
                if not sc.has_work():
                    self.shadow.assert_drained()
            self._finalize_outputs(outs)
            return plan.events + outs

    def _validate_tokens(self, plan: StepPlan, tok_np: np.ndarray) -> None:
        """Reject a step whose *consumable* rows carry out-of-range tokens —
        the ``NONFINITE_TOKEN`` sentinel the jitted impls substitute when a
        row's logits contain NaN/Inf, or garbage from an injected fault.
        Only rows whose sample would actually be consumed are checked: live
        owner, not budget-stalled, and (for chunked rows) completing their
        prompt this step — a poisoned mid-prompt row's sample is discarded
        anyway.  Raises :class:`StepFailure` naming the poisoned rows,
        before any scheduler/request mutation."""
        sc = self.sched
        bad_slots: List[int] = []
        bad_uids: List[int] = []
        for slot in plan.active:
            req = sc.slots[slot]
            if req is None or req.uid != plan.owners.get(slot):
                continue               # discarded at commit anyway
            if slot in plan.stalled:
                continue               # emit-less pad row
            n = plan.chunks.get(slot)
            if n is not None and n < len(sc.pending[slot]):
                continue               # mid-prompt chunk: sample discarded
            t = int(tok_np[slot])
            if t < 0 or t >= self.cfg.padded_vocab:
                bad_slots.append(slot)
                bad_uids.append(req.uid)
        if bad_slots:
            raise StepFailure(
                f"step produced non-finite/out-of-range tokens for slots "
                f"{bad_slots} (uids {bad_uids}); plan is safe to relaunch",
                uids=bad_uids, slots=bad_slots)

    def plan_stale(self, plan: StepPlan) -> bool:
        """True when ``plan`` no longer matches live scheduler state — a
        request it covers was cancelled / expired / preempted since it was
        planned (its slot freed or re-assigned, or its pending prompt
        consumed).  A failed step's plan is only safe to *relaunch* verbatim
        while fresh: chunk rows re-materialize their tokens from
        ``sched.pending``, so a stale plan must be replanned instead (the
        supervisor's retry path checks this between failure and relaunch —
        the cancel-races-retry window)."""
        sc = self.sched
        for slot in plan.active:
            req = sc.slots[slot]
            if req is None or req.uid != plan.owners.get(slot):
                return True
            n = plan.chunks.get(slot)
            if n is not None and n > len(sc.pending[slot]):
                return True
        return False

    def quarantine(self, uid: int) -> Optional[StepOutput]:
        """Finish a repeatedly-failing request with ``FinishReason.ERROR``
        (the supervisor's last resort once retries keep tracing a failure to
        the same row): tokens generated so far are kept, the slot frees and
        its blocks release exactly like a cancel, and the engine keeps
        serving everyone else."""
        return self.cancel(uid, FinishReason.ERROR)

    def shed_queued(self, keep: int) -> List[StepOutput]:
        """Graceful-degradation load shedding: drop waiting (not yet
        admitted) requests beyond the ``keep`` newest-last until the queue is
        that short, finishing each with an ``ABORTED`` marker event.  Sheds
        from the back of the queue, so the oldest waiters (including
        preemption re-queues, which re-enter at the front) keep their place.
        Returns the finalized marker events."""
        outs: List[StepOutput] = []
        sc = self.sched
        while len(sc.waiting) > max(0, keep):
            req = sc.waiting.pop()
            sc._arrival.pop(req.uid, None)
            req.finish_reason = FinishReason.ABORTED
            outs.append(StepOutput(uid=req.uid, token=-1,
                                   index=req.num_generated, finished=True,
                                   finish_reason=FinishReason.ABORTED))
            self._load_sheds += 1
        if outs and self.recorder is not None:
            self.recorder.record("load_shed", count=len(outs),
                                 kept=max(0, keep))
        self._finalize_outputs(outs)
        return outs

    def _sanitize_writes(self, plan: StepPlan) -> List[int]:
        """Check the step's KV write-set against the shadow pool before
        dispatch: a chunked slot writes positions ``[start, start+n)``, a
        decode (or budget-stalled pad) row writes position ``start`` — every
        logical block those positions map to must be the trash block or a
        block the slot owns exclusively.  Shared/published prefix blocks are
        immutable; catching an attempt *here* names the faulting slot and
        block instead of surfacing later as cross-request corruption.
        Returns the deduplicated physical write-set (trash excluded) so the
        ``kv_checksums`` commit can digest exactly what this step wrote."""
        sc = self.sched
        bs = self.allocator.block_size
        width = sc.block_tables.shape[1]
        written: List[int] = []
        for slot in plan.active:
            start = int(plan.positions[slot])
            n = plan.chunks.get(slot, 1)
            # positions >= max_len are never written (LENGTH fires first);
            # unallocated trailing blocks map to trash, which is writable
            first = min(start // bs, width - 1)
            last = min((start + n - 1) // bs, width - 1)
            for lb in range(first, last + 1):
                b = int(sc.block_tables[slot, lb])
                self.shadow.check_write(slot, b)
                if b != 0 and b not in written:       # 0 == TRASH_BLOCK
                    written.append(b)
        return written

    def _launch_decode(self, plan: StepPlan, feed: Optional[InflightStep]
                       ) -> Tuple[jax.Array, StepWork]:
        """Pure-decode dispatch (no prefilling slots): the paged_attention
        decode kernel / gather path, one token per active slot."""
        sc, b = self.sched, self.scfg.max_batch
        positions = plan.positions
        bt = None
        width = None
        walked = live = 0
        if self.paged:
            # gather only the blocks covering the deepest active row
            # (power-of-two widths bound retraces, like chunk buckets) —
            # per-step KV gather bandwidth then tracks the batch's actual
            # depth instead of max_len
            depth = int(positions[plan.active].max()) + 1
            width = bucket_length(self.allocator.blocks_for(depth), 1,
                                  sc.block_tables.shape[1])
            bt = jnp.asarray(sc.block_tables[:, :width])
            walked = b * width
            live = sum(self.allocator.blocks_for(int(positions[s]) + 1)
                       for s in plan.active)
        work = StepWork("decode", len(plan.active), b, len(plan.active),
                        walked, live, walked)
        # snapshot of the step shape actually run (post-admission,
        # pre-record), which the serving benchmark counts work from
        self.last_decode = {"active": list(plan.active),
                            "positions": positions.tolist(),
                            "table_width": width,
                            "chunks": None}
        # a speculative launch feeds the previous step's sampled tokens as a
        # device array — no host sync; keys and cache already flow through
        # self._keys / self._cache as unmaterialized step-N outputs
        toks_in = (feed.tok if plan.spec and feed is not None
                   else jnp.asarray(self._tokens))
        tok, self._cache, self._keys = self._decode(
            self.params, toks_in, self._cache,
            jnp.asarray(positions), self._keys,
            jnp.asarray(sc.temperatures), jnp.asarray(sc.top_ps), bt)
        return tok, work

    def _launch_chunk(self, plan: StepPlan) -> Tuple[jax.Array, StepWork]:
        """Fused chunk-step dispatch: prefilling slots advance their planned
        chunk, decoding slots their one token, in a single jitted call.
        Budget-stalled mid-prefill slots ride along as emit-less length-1 pad
        rows: their stale KV write at the current fill position is rewritten
        bit-identically by the real chunk before anything attends it, and
        ``emit=False`` keeps their PRNG stream untouched."""
        sc, scfg = self.sched, self.scfg
        chunks = plan.chunks
        # chunk widths bucket to powers of two (bounds recompiles to
        # O(log prefill_chunk) shapes); whole-prompt mode buckets by
        # prefill_bucket_min exactly like the retired admission prefill
        max_l = max(chunks.values()) if chunks else 1
        if scfg.prefill_chunk > 0:
            t = bucket_length(max_l, 1, scfg.prefill_chunk)
        else:
            t = bucket_length(max_l, scfg.prefill_bucket_min, scfg.max_len)
        toks = np.full((scfg.max_batch, t), scfg.pad_id, np.int32)
        start = plan.positions.copy()
        lens = np.ones((scfg.max_batch,), np.int32)
        emit = np.zeros((scfg.max_batch,), bool)
        for slot in plan.active:
            n = chunks.get(slot)
            if n is not None:
                toks[slot, :n] = sc.pending[slot][:n]
                lens[slot] = n
                emit[slot] = n == len(sc.pending[slot])  # prompt exhausted
            elif slot in plan.stalled:
                pass                   # emit-less pad row (see docstring)
            else:
                toks[slot, 0] = self._tokens[slot]
                emit[slot] = True
        bt = None
        width = None
        walked = live = scored = 0
        if self.paged:
            depth = max(int(start[s]) + int(lens[s]) for s in plan.active)
            width = bucket_length(self.allocator.blocks_for(depth), 1,
                                  sc.block_tables.shape[1])
            table = sc.block_tables[:, :width]
            bt = jnp.asarray(table)
            walked = scored = scfg.max_batch * width
            live = sum(self.allocator.blocks_for(int(start[s]) + int(lens[s]))
                       for s in plan.active)
            if self.attn_impl == "fused" and scfg.prefill_chunk > 0:
                # the prefill kernel's fetch rule (imported here, as
                # nn/attention.py imports the kernel: the kernel layer
                # imports serving modules)
                from repro.kernels.paged_prefill.ops import fetched_blocks
                scored = sum(int(m.sum()) for m in fetched_blocks(
                    start, lens, table, self.allocator.block_size))
        useful = sum(int(lens[s]) for s in plan.active
                     if s not in plan.stalled)
        work = StepWork("chunk", len(plan.active), scfg.max_batch * t, useful,
                        walked, live, scored)
        self.last_decode = {"active": list(plan.active),
                            "positions": start.tolist(),
                            "table_width": width,
                            "chunks": dict(chunks), "chunk_t": t,
                            "starts": start.tolist(), "lens": lens.tolist()}
        args = (self.params, jnp.asarray(toks), self._cache,
                jnp.asarray(start), jnp.asarray(lens), jnp.asarray(emit),
                self._keys, jnp.asarray(sc.temperatures),
                jnp.asarray(sc.top_ps))
        if self.paged:
            # prefill_chunk == 0 is the stop-the-world baseline: the legacy
            # sequential whole-prompt scan, not the fused chunk attention
            fn = self._chunk if scfg.prefill_chunk > 0 else self._chunk_scan
            tok, self._cache, self._keys = fn(*args, bt)
        else:
            tok, self._cache, self._keys = self._chunk_scan(*args)
        return tok, work

    # -- cancellation / deadlines ----------------------------------------------

    # -- device-memory integrity (ServeConfig.kv_checksums) --------------------

    def _kv_block_digests(self, blocks: Sequence[int]) -> Dict[int, int]:
        """crc32 over every cache leaf's rows for each requested pool block.
        Transfers the pool to the host — the documented kv_checksums debug
        cost, in the same price class as the sanitizer's per-step checks."""
        self._ensure_state()
        host = [np.asarray(leaf)  # lint: allow(host-sync) kv_checksums sweep
                for leaf in jax.tree_util.tree_leaves(self._cache)]
        out: Dict[int, int] = {}
        for b in blocks:
            crc = 0
            for h in host:
                # paged pool leaves are [layers, num_blocks, Hkv, bs, Dh]:
                # axis 1 is the block axis (kv_checksums implies paged)
                crc = zlib.crc32(h[:, b].tobytes(), crc)
            out[int(b)] = crc
        return out

    def check_kv_integrity(self) -> List[int]:
        """Sweep every resident checksummed block for silent device-memory
        corruption: recompute content digests and compare against the
        digests the shadow recorded at write time.  Returns the corrupt
        block ids (empty without ``ServeConfig(kv_checksums=True)``).
        Detection is *reported*, not raised — pass the result to
        :meth:`recover_corrupt_blocks` for targeted recompute-preemption."""
        if self.shadow is None or not self.shadow.checksums_enabled:
            return []
        blocks = self.shadow.checksummed()
        if not blocks:
            return []
        bad = self.shadow.verify_checksums(self._kv_block_digests(blocks))
        if bad:
            self._kv_corruptions += len(bad)
            if self.recorder is not None:
                self.recorder.record("kv_corruption", blocks=len(bad))
        return bad

    def recover_corrupt_blocks(self, blocks: Sequence[int]) -> List[int]:
        """Targeted recovery from KV corruption: preempt every slot whose
        block table references a corrupt block (owner *or* shared reader) —
        recompute re-prefill of prompt + committed tokens rebuilds the KV
        bit-identically, so greedy outputs keep parity — and flush the
        prefix cache if a corrupt block stayed published after the readers
        were preempted.  The freed blocks' stale digests clear on free and
        their garbage content is fully overwritten before the next read
        (prefill/decode fill blocks front-to-back).  Returns the preempted
        uids."""
        bad = {int(b) for b in blocks}
        if not bad:
            return []
        sc = self.sched
        uids: List[int] = []
        for slot in list(sc.active_slots()):
            table = sc.block_tables[slot]
            if any(int(table[i]) in bad for i in range(table.shape[0])):
                req = sc.slots[slot]
                uids.append(req.uid)
                sc._preempt(slot)
        if self.prefix_cache is not None and self.allocator is not None and \
                any(int(self.allocator.refcounts[b]) > 0 for b in bad):
            # still-referenced corrupt blocks can only be trie holds now;
            # there is no per-block trie removal, so drop the whole cache —
            # corruption is rare and a cold cache only costs re-prefill
            self.prefix_cache.clear()
        if self.recorder is not None:
            self.recorder.record("kv_corruption_recovered",
                                 blocks=len(bad), preempted=len(uids))
        return uids

    def corrupt_kv_block(self, block: int, seed: int = 0,
                         mode: str = "garbage") -> None:
        """Fault-injection helper (faults.py ``device_mem`` site): overwrite
        one pool block's KV rows behind the allocator protocol — seeded
        garbage (``mode='garbage'``) or a single bit flip
        (``mode='bitflip'``) — simulating silent device-memory corruption.
        Never called in production paths."""
        self._ensure_state()
        rng = np.random.default_rng(seed)

        def garble(leaf):
            # block axis is 1 ([layers, num_blocks, Hkv, bs, Dh])
            row = np.asarray(leaf[:, block])  # lint: allow(host-sync) injector
            if mode == "bitflip":
                flat = np.ascontiguousarray(row).view(np.uint8).reshape(-1).copy()
                i = int(rng.integers(flat.size))
                flat[i] ^= np.uint8(1 << int(rng.integers(8)))
                new = flat.view(row.dtype).reshape(row.shape)
            else:
                new = rng.standard_normal(row.shape).astype(row.dtype)
            return leaf.at[:, block].set(jnp.asarray(new))

        self._cache = jax.tree_util.tree_map(garble, self._cache)

    def cancel(self, uid: int,
               reason: FinishReason = FinishReason.CANCELLED
               ) -> Optional[StepOutput]:
        """End a request from the outside — queued, mid-prefill, or
        mid-decode.  The slot is freed immediately and its blocks released
        (to the prefix cache when enabled: even a half-prefilled prompt's
        published progress stays resident).  Emits the terminal marker
        StepOutput (token == -1) through the request's callback and returns
        it; returns None if the uid is not in flight.  No further StepOutputs
        are ever emitted for the uid — a step in flight when the cancel lands
        has its row discarded at commit (owner check)."""
        req = self._requests.get(uid)
        if req is None or req.done:
            return None
        out = self.sched.cancel(uid, reason)
        if out is None:                # defensive: unknown to the scheduler
            self._requests.pop(uid, None)
            self._submit_ts.pop(uid, None)
            return None
        if reason == FinishReason.DEADLINE:
            self._deadline_expirations += 1
        elif reason == FinishReason.ERROR:
            self._quarantines += 1
        else:
            self._cancellations += 1
        if self.recorder is not None:
            self.recorder.record("cancel", uid=uid, reason=reason.name)
        self._finalize_outputs([out])
        return out

    def expire_deadlines(self) -> List[StepOutput]:
        """Finish every in-flight request whose deadline has passed with
        ``FinishReason.DEADLINE`` (queued, mid-prefill, and mid-decode alike).
        Called at every plan boundary; the async loop also sweeps between
        speculative launches.  Returns the (already finalized) marker
        events."""
        now = self.clock.now()
        expired = [req.uid for req in self._requests.values()
                   if req.deadline is not None and now >= req.deadline]
        outs = []
        for uid in expired:
            out = self.cancel(uid, FinishReason.DEADLINE)
            if out is not None:
                outs.append(out)
        return outs

    def _finalize_outputs(self, outs: List[StepOutput]) -> None:
        """Per-output bookkeeping: latency samples (TTFT at the first real
        token, queue-wait at admission elsewhere, end-to-end at finish),
        token counters, the per-request callback, and in-flight map cleanup."""
        if not outs:
            return
        if self.journal is not None:
            # write-ahead: the batch is durable before any callback can
            # deliver it, so the journal is a superset of what clients saw —
            # a resuming client's offset always lands inside replayed state
            batch: Dict[int, List[int]] = {}
            for out in outs:
                if out.token >= 0:
                    batch.setdefault(out.uid, []).append(out.token)
            self.journal.log_tokens(batch)
            for out in outs:
                if out.finished:
                    req = self._requests.get(out.uid)
                    n = req.num_generated if req is not None else 0
                    self.journal.log_terminal(out.uid, out.finish_reason, n)
            self.journal.commit()
        now = self.clock.now()
        for out in outs:
            if out.token >= 0:
                self._tokens_generated += 1
                if out.index == 0:
                    t0 = self._submit_ts.get(out.uid)
                    if t0 is not None:
                        self._ttft_ms.observe((now - t0) * 1e3)
                    if self.tracer is not None:
                        self.tracer.request_first_token(out.uid, now)
            req = self._requests.get(out.uid)
            if req is not None and req.on_token is not None:
                req.on_token(out)
            if out.finished:
                t0 = self._submit_ts.pop(out.uid, None)
                if t0 is not None:
                    self._e2e_ms.observe((now - t0) * 1e3)
                if self.tracer is not None:
                    # every terminal path (finish / cancel / deadline /
                    # quarantine / shed / rejection) funnels through here,
                    # so the root span always closes
                    reason = (out.finish_reason.name.lower()
                              if out.finish_reason is not None else "stop")
                    tokens = req.num_generated if req is not None else 0
                    self.tracer.request_finish(out.uid, now, reason, tokens)
                self._requests.pop(out.uid, None)

    def stream(self) -> Iterator[StepOutput]:
        """Drive steps until all submitted work finishes, yielding tokens in
        generation order (interleaved across requests)."""
        while self.sched.has_work():
            for out in self.step():
                yield out

    # -- compatibility wrapper ------------------------------------------------------

    def generate(self, requests: Sequence[Union[Request, GenerationRequest]]
                 ) -> Dict[int, List[int]]:
        """Blocking run-to-completion over a request list (legacy API).
        Accepts old-style :class:`Request` (mirrors results into ``.output``/
        ``.done``) or :class:`GenerationRequest`.

        Note the semantics change from the pre-continuous-batching engine:
        ``ServeConfig.max_len`` is the per-slot cache capacity (prompt +
        generated), no longer a generated-token budget on top of a cache
        sized to the prompt.  Legacy Requests have no finish_reason to
        surface an admission rejection on, so an oversized prompt raises
        here instead of silently returning an empty output."""
        legacy: Dict[int, Request] = {}
        handles: Dict[int, GenerationRequest] = {}

        def rejected(prompt):
            if not prompt or len(prompt) + 1 > self.scfg.max_len:
                return True
            return (self.allocator is not None and
                    self.allocator.blocks_for(len(prompt) + 1)
                    > self.allocator.allocatable)

        bad = [r.uid for r in requests
               if not isinstance(r, GenerationRequest) and rejected(r.prompt)]
        if bad:
            raise ValueError(
                f"prompts of requests {bad} are empty or exceed the per-slot "
                f"cache capacity (ServeConfig.max_len={self.scfg.max_len}, "
                "which counts prompt + generated tokens) or the paged KV "
                "pool (ServeConfig.num_kv_blocks)")
        for r in requests:
            if isinstance(r, GenerationRequest):
                self.submit_request(r)
                handles[r.uid] = r
            else:
                params = SamplingParams(max_tokens=r.max_tokens,
                                        temperature=self.scfg.temperature,
                                        top_p=self.scfg.top_p)
                handles[r.uid] = self.submit(r.prompt, params, uid=r.uid)
                legacy[r.uid] = r
        for _ in self.stream():
            pass
        results = {uid: list(h.output_tokens) for uid, h in handles.items()}
        for uid, r in legacy.items():
            r.output = results[uid]
            r.done = handles[uid].done
        return results

    # -- internals ---------------------------------------------------------------

    def _ensure_state(self):
        if self._cache is None:
            if self.paged:
                # the block pool *is* an init_cache with batch=num_blocks and
                # per-"row" length block_size: [R, N, Hkv, bs, Dh] per layer
                self._cache = self.model.init_cache(
                    self.params, self.scfg.pool_blocks(),
                    self.scfg.kv_block_size, jnp.dtype(self.scfg.cache_dtype))
            else:
                self._cache = self.model.init_cache(
                    self.params, self.scfg.max_batch, self.scfg.max_len,
                    jnp.dtype(self.scfg.cache_dtype))
            self._keys = jnp.zeros((self.scfg.max_batch, 2), jnp.uint32)

    def stats(self) -> EngineStats:
        """Snapshot of the engine's runtime counters: admissions,
        preemptions, chunked-prefill work (positions run per chunk vs
        positions skipped via prefix sharing, chunk count), paged-block
        occupancy, latency percentiles (TTFT, queue wait, end-to-end),
        host dispatch-gap / overlap accounting, cancellation and deadline
        counters, and — with ``ServeConfig(prefix_cache=True)`` — the
        radix-cache hit/miss/eviction counters.

        Cheap to call mid-run: latency series live in fixed-memory
        log-bucketed histograms (serving/telemetry.py), so rendering is
        O(buckets) with no list copies, and *every* series guards the
        empty case the same way — ``None`` until the first sample,
        ``{"mean","p50","p95","p99"}`` after (single-sample series
        render that sample exactly).  The live metric names behind each
        field are listed in the README's Observability catalog;
        ``Engine.metrics.snapshot()`` serves the same numbers without
        building an EngineStats."""
        alloc = self.allocator

        def pct(h: Histogram) -> Optional[Dict[str, float]]:
            return h.percentiles() if h.count else None

        return EngineStats(
            requests_submitted=self._requests_submitted,
            admissions=self.sched.admissions,
            preemptions=self.sched.preemptions,
            prefill_positions=self._prefill_positions,
            prefill_positions_skipped=self._prefill_skipped,
            prefill_chunks=self._prefill_chunks,
            chunk_positions_run=self._chunk_positions_run,
            chunk_positions_useful=self._chunk_positions_useful,
            kv_blocks_walked=self._kv_blocks_walked,
            kv_blocks_live=self._kv_blocks_live,
            kv_blocks_scored=self._kv_blocks_scored,
            tokens_generated=self._tokens_generated,
            queue_depth=len(self.sched.waiting),
            cancellations=self._cancellations,
            deadline_expirations=self._deadline_expirations,
            steps_committed=self._steps_committed,
            steps_overlapped=self._steps_overlapped,
            ttft_ms=pct(self._ttft_ms),
            queue_wait_ms=pct(self._queue_wait_ms),
            e2e_latency_ms=pct(self._e2e_ms),
            step_gap_ms=pct(self._step_gap_ms),
            blocks_in_use=None if alloc is None else alloc.blocks_in_use(),
            blocks_free=None if alloc is None else alloc.available(),
            prefix_cache=(None if self.prefix_cache is None
                          else self.prefix_cache.stats()),
            sanitizer=(None if self.shadow is None
                       else self.shadow.stats()),
            step_failures=self._step_failures,
            step_retries=self._step_retries,
            quarantines=self._quarantines,
            engine_restarts=self._engine_restarts,
            load_sheds=self._load_sheds,
            hung_steps=self._hung_steps,
            degrade_tier=self._degrade_tier,
            recovery_ms=pct(self._recovery_ms),
            kv_corruptions=self._kv_corruptions,
            journal_records=(None if self.journal is None
                             else self.journal.appended),
            journal_commits=(None if self.journal is None
                             else self.journal.commits),
            journal_replays=(None if self.journal is None
                             else self.journal.state.recoveries))

    def kv_cache_bytes(self) -> int:
        """Resident KV-cache bytes of the live decode state (the paged pool
        or the contiguous [slots, max_len] regions)."""
        self._ensure_state()
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            self._cache))

    def _request_key(self, req: GenerationRequest) -> jax.Array:
        seed = req.params.seed
        if seed is None:
            seed = (self.scfg.seed + 0x9E3779B9 * (req.uid + 1)) & 0x7FFFFFFF
        return jax.random.PRNGKey(seed)


# retained name: the pre-continuous-batching engine class
ServingEngine = Engine


def logits_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# -- packed-weight conversion ----------------------------------------------------

def convert_to_packed(cfg: ModelConfig, qat_params) -> Tuple[ModelConfig, dict]:
    """QAT student -> packed ternary serving artifact.

    Every BitLinear weight leaf 'w' [K, N] under a quantized module becomes
    {'w_packed' uint8 [K/4, N], 'delta' f32[]} — 8x smaller than bf16 and
    16x smaller than fp32 master weights.
    """
    from repro.core.bitlinear import convert_linear_params_fp_to_packed
    from repro.core import quant as Q

    packed_cfg = cfg.replace(quant=dataclasses.replace(cfg.quant, mode="packed"))
    model_p = build_model(packed_cfg)
    tmpl = model_p.init(jax.random.PRNGKey(0))

    def walk(src, dst):
        if isinstance(dst, dict):
            if set(dst.keys()) >= {"w_packed", "delta"} and "w" in src:
                w = src["w"]
                if w.shape[-2] % 4 != 0:
                    return dst  # non-packable (K % 4 != 0) stays at init
                # scanned stacks hold [repeats, ..., K, N]: each layer packs
                # on its own, with its own delta
                conv = convert_linear_params_fp_to_packed
                for _ in range(w.ndim - 2):
                    conv = jax.vmap(conv)
                out = conv(w)
                return {**{k: walk(src.get(k), v) for k, v in dst.items()
                           if k not in out}, **out}    # keeps the bias
            return {k: walk(src.get(k, None), v) if isinstance(src, dict)
                    else v for k, v in dst.items()}
        if src is not None and hasattr(src, "shape") and \
                tuple(src.shape) == tuple(dst.shape):
            return jnp.asarray(src, dst.dtype)
        return dst

    return packed_cfg, walk(qat_params, tmpl)
