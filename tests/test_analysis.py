"""repro.analysis: the lint rules fire on seeded violations (and stay quiet
on the repo), the shadow block pool catches seeded protocol mutations at
engine level, and the retrace watchdog proves steady-state decode compiles
each jitted impl exactly once per signature.

The lint tests build tiny synthetic source trees in tmp_path — each rule
gets a minimal positive (must fire) and the repo itself is the negative
(must be clean modulo the checked-in baseline).  The mutation tests are the
ISSUE's acceptance criterion: seeding a real protocol violation into a live
engine (a scatter into a published block; a trie reference dropped without
eviction) must raise SanitizerError.
"""
import pathlib
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis.lint import Linter, run_lint
from repro.analysis.retrace import RetraceError, RetraceWatchdog
from repro.analysis.shadow import (BlockState, SanitizerError,
                                   ShadowBlockPool)
from repro.models import build_model, get_config
from repro.serving.api import SamplingParams
from repro.serving.engine import Engine, ServeConfig


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("qwen1.5-0.5b").reduced(layers=2).replace(
        compute_dtype="float32", param_dtype="float32")
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def _engine(lm, **kw):
    cfg, params = lm
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("paged", True)
    return Engine(cfg, params, ServeConfig(**kw))


# -- static lint --------------------------------------------------------------


def _tree(tmp_path: pathlib.Path, files) -> Linter:
    """Materialize {relpath: source} under tmp_path/src/repro and lint it."""
    root = tmp_path / "src" / "repro"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Linter(root)


def _rules(linter: Linter, suppressed: bool = False):
    return sorted({f.rule for f in linter.run()
                   if linter.is_suppressed(f) == suppressed})


class TestLintRules:
    def test_repo_is_clean_modulo_baseline(self):
        res = run_lint()
        assert res.ok, "\n".join(f.render() for f in res.active)
        # the intended suppressions exist and nothing else is suppressed
        assert sorted({f.rule for f in res.suppressed}) == \
            ["host-sync", "pallas-grid-div"]

    def test_bare_assert_fires(self, tmp_path):
        lint = _tree(tmp_path, {"mod.py": """
            def f(x):
                assert x > 0
                return x
        """})
        assert _rules(lint) == ["bare-assert"]

    def test_host_sync_reachable_from_hot_path(self, tmp_path):
        lint = _tree(tmp_path, {"serving/engine.py": """
            import numpy as np

            def helper(x):
                return np.asarray(x)      # reached via plan_step -> helper

            def cold(x):
                return np.asarray(x)      # NOT reachable: no finding

            class Engine:
                def plan_step(self):
                    return helper(self.tok)
        """})
        fs = [f for f in lint.run() if f.rule == "host-sync"]
        assert [f.symbol for f in fs] == ["helper"]

    def test_host_sync_by_reference_and_item(self, tmp_path):
        lint = _tree(tmp_path, {"serving/async_engine.py": """
            import numpy as np

            class AsyncEngine:
                async def _loop(self, ex, tok):
                    a = await ex.run(np.asarray, tok)   # passed by reference
                    return a.item()                     # sync method call
        """})
        msgs = [f.message for f in lint.run() if f.rule == "host-sync"]
        assert len(msgs) == 2
        assert any("passed by reference" in m for m in msgs)

    def test_host_sync_suppression_comment(self, tmp_path):
        lint = _tree(tmp_path, {"serving/engine.py": """
            import numpy as np

            class Engine:
                def commit_step(self):
                    # lint: allow(host-sync) the one budgeted sync
                    return np.asarray(self.tok)
        """})
        assert _rules(lint) == []
        assert _rules(lint, suppressed=True) == ["host-sync"]

    def test_telemetry_alloc_in_hot_path(self, tmp_path):
        """Container-building arguments to tracer/recorder calls fire only
        when the call is reachable from the engine's hot path; scalar
        arguments never fire."""
        lint = _tree(tmp_path, {"serving/engine.py": """
            class Engine:
                def commit_step(self, step):
                    self.tracer.commit_span(0.0, 1.0, step)          # scalars
                    self.recorder.record("commit", uids=[1, 2])
                    self.recorder.record("note", msg=f"step {step}")

                def post_mortem(self):
                    # cold path: same pattern, no finding
                    return self.recorder.dump("done", uids=list(self._u))
        """})
        fs = [f for f in lint.run() if f.rule == "telemetry-alloc"]
        assert len(fs) == 2
        assert all(f.symbol == "Engine.commit_step" for f in fs)
        assert any("list literal" in f.message for f in fs)
        assert any("f-string" in f.message for f in fs)

    def test_telemetry_alloc_suppression(self, tmp_path):
        lint = _tree(tmp_path, {"serving/engine.py": """
            class Engine:
                def plan_step(self):
                    # lint: allow(telemetry-alloc) dumped once per fault
                    self.recorder.record("plan", uids=[1])
        """})
        assert _rules(lint) == []
        assert _rules(lint, suppressed=True) == ["telemetry-alloc"]

    def test_jit_traced_control_flow_fires(self, tmp_path):
        lint = _tree(tmp_path, {"kernels/k/kernel.py": """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n, flag):
                if flag:                  # traced param in Python control flow
                    return x * n
                return x

            @functools.partial(jax.jit, static_argnames=("n",))
            def ok(x, n):
                if n > 4:                 # static param: fine
                    return x * n
                return x
        """})
        fs = [f for f in lint.run() if f.rule == "jit-traced-control-flow"]
        assert [f.symbol for f in fs] == ["f"]

    def test_jit_static_unhashable_default_and_call(self, tmp_path):
        lint = _tree(tmp_path, {"mod.py": """
            import jax

            @jax.jit
            def plain(x):
                return x

            import functools

            @functools.partial(jax.jit, static_argnames=("cfg",))
            def f(x, cfg=[1, 2]):
                return x

            def caller(x):
                return f(x, cfg=[3, 4])
        """})
        fs = [f for f in lint.run() if f.rule == "jit-static-unhashable"]
        assert len(fs) == 2               # the default and the call site

    def test_pallas_alias_fires_on_uncovered_scatter(self, tmp_path):
        src = """
        import jax
        from jax.experimental import pallas as pl

        def k(x, pool, bn=8, interpret=False):
            return pl.pallas_call(
                _body,
                grid=(pl.cdiv(x.shape[0], bn),),
                in_specs=[pl.BlockSpec((bn, 128), lambda i: (i, 0)),
                          pl.BlockSpec((bn, 128), lambda i: (i, 0))],
                out_specs=[pl.BlockSpec((bn, 128), lambda i: (i, 0)),
                           pl.BlockSpec((bn, 128), lambda i: (i, 0))],
                out_shape=[jax.ShapeDtypeStruct((8, 128), x.dtype),
                           jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
                {ALIAS}
                interpret=interpret,
            )(x, pool)
        """
        aliased = _tree(tmp_path / "a", {"kernels/k/kernel.py":
                        src.replace("{ALIAS}",
                                    "input_output_aliases={1: 1},")})
        assert "pallas-alias" not in _rules(aliased)
        bare = _tree(tmp_path / "b", {"kernels/k/kernel.py":
                     src.replace("{ALIAS}", "")})
        assert "pallas-alias" in _rules(bare)

    def test_pallas_arity_and_align_and_grid_div(self, tmp_path):
        lint = _tree(tmp_path, {"kernels/k/kernel.py": """
            import jax
            from jax.experimental import pallas as pl

            def k(x, n, interpret=False):
                return pl.pallas_call(
                    _body,
                    grid=(n // 4,),
                    in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0)),
                              pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
                )(x)
            """})
        got = _rules(lint)
        assert "pallas-arity" in got      # 2 in_specs, 1 operand
        assert "pallas-align" in got      # last dim 100: not 1 / x128
        assert "pallas-grid-div" in got   # n // 4 in the grid

    @pytest.mark.parametrize("block,index,flagged", [
        # a 1 over the kv-head axis the map walks: Mosaic refuses it
        ("(1, 1, dh)", "(b, h, 0)", True),
        # the same row with a unit axis of its own: block == array dim
        ("(1, 1, 1, dh)", "(b, h, 0, 0)", False),
    ])
    def test_pallas_align_sublane_one(self, tmp_path, block, index, flagged):
        lint = _tree(tmp_path, {"kernels/k/kernel.py": f"""
            import jax
            from jax.experimental import pallas as pl

            def k(x, dh, interpret=False):
                return pl.pallas_call(
                    _body,
                    grid=(4, 2),
                    in_specs=[pl.BlockSpec({block}, lambda b, h: {index})],
                    out_specs=pl.BlockSpec({block}, lambda b, h: {index}),
                    out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                )(x)
            """})
        assert ("pallas-align" in _rules(lint)) == flagged

    def test_kernel_ref_parity(self, tmp_path):
        files = {"kernels/k/kernel.py": """
            def fused_op_kernel(x, w, bm=8, interpret=False):
                return x
        """, "kernels/k/ref.py": """
            def fused_op_ref(x, w):
                return x
        """}
        assert "kernel-ref-parity" not in _rules(_tree(tmp_path / "a", files))
        files["kernels/k/ref.py"] = """
            def fused_op_ref(w, x):      # transposed params: not a subsequence
                return x
        """
        assert "kernel-ref-parity" in _rules(_tree(tmp_path / "b", files))

    def test_baseline_grandfathers_by_count(self, tmp_path):
        import json

        from repro.analysis import lint as L
        root = tmp_path / "src" / "repro"
        (root / "pkg").mkdir(parents=True)
        (root / "pkg" / "m.py").write_text(
            "def f(x):\n    assert x\n    assert x > 1\n")
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps(
            {"entries": {"bare-assert::src/repro/pkg/m.py::f": 1}}))
        res = L.run_lint(root, bl)
        assert len(res.baselined) == 1    # one grandfathered...
        assert len(res.active) == 1       # ...the second assert still fails


# -- shadow block pool --------------------------------------------------------


class TestShadowUnit:
    def test_clean_lifecycle_states(self):
        sh = ShadowBlockPool(6, 4)
        sh.on_alloc([1, 2])
        sh.claim(0, [1, 2])
        assert sh.state[1] is BlockState.OWNED and sh.owner[1] == 0
        sh.on_share(1, 2)
        sh.publish(1)
        assert sh.state[1] is BlockState.SHARED
        sh.on_free(1, 1)                  # slot drops its reference
        assert sh.state[1] is BlockState.PUBLISHED
        sh.unpublish(1)
        sh.on_free(1, 0)
        assert sh.state[1] is BlockState.FREE

    def test_refcount_mismatch_detected(self):
        sh = ShadowBlockPool(6, 4)
        sh.on_alloc([1])
        with pytest.raises(SanitizerError, match="refcount"):
            sh.on_share(1, 5)             # allocator claims 5, mirror says 2

    def test_verify_against_real_allocator(self):
        from repro.serving.paged import BlockAllocator
        alloc = BlockAllocator(6, 4)
        sh = ShadowBlockPool(6, 4)
        alloc.observer = sh
        ids = alloc.alloc(2)
        sh.claim(0, ids)
        sh.verify(alloc)                  # consistent
        alloc.refcounts[ids[0]] += 1      # bypass the protocol
        with pytest.raises(SanitizerError, match="refcount"):
            sh.verify(alloc)


class TestSeededMutations:
    """ISSUE acceptance: seeded protocol violations in a *live* engine are
    caught by the sanitizer."""

    def _run(self, eng, prompts, max_tokens=4):
        sp = SamplingParams(max_tokens=max_tokens, ignore_eos=True)
        reqs = [eng.submit(p, sp) for p in prompts]
        for _ in eng.stream():
            pass
        return reqs

    def test_scatter_into_published_block_caught(self, lm):
        """Mutation: between plan and launch, point a slot's block table at
        a published prefix block — the write-set check must refuse."""
        eng = _engine(lm, prefill_chunk=4, prefix_cache=True, sanitize=True)
        self._run(eng, [list(range(1, 11))])   # publishes two full blocks
        published = sorted(eng.shadow._published)
        assert published, "prefix cache published nothing"
        eng.submit([20, 21, 22, 23, 24],       # no prefix match
                   SamplingParams(max_tokens=4, ignore_eos=True))
        plan = eng.plan_step()
        assert plan.active
        slot = plan.active[0]
        # seed the corruption: retarget the logical block this chunk writes
        lb = int(plan.positions[slot]) // eng.allocator.block_size
        eng.sched.block_tables[slot, lb] = published[0]
        with pytest.raises(SanitizerError, match="about to write"):
            eng.launch_step(plan)

    def test_dropped_trie_reference_caught(self, lm):
        """Mutation: free a published cached-but-unreferenced block directly
        (a dropped share() without evicting the trie node) — the shadow
        must refuse to let it recycle onto the free list."""
        eng = _engine(lm, prefill_chunk=4, prefix_cache=True, sanitize=True)
        self._run(eng, [list(range(1, 9))])
        eng.shadow.assert_drained()
        cached = [b for b in eng.shadow._published
                  if eng.shadow.state[b] is BlockState.PUBLISHED]
        assert cached
        with pytest.raises(SanitizerError, match="published block"):
            eng.allocator.free([cached[0]])

    def test_clean_run_is_silent_and_drains(self, lm):
        eng = _engine(lm, prefill_chunk=4, prefix_cache=True, sanitize=True)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, int(rng.integers(3, 12))).tolist()
                   for _ in range(5)]
        self._run(eng, prompts)
        eng.shadow.assert_drained()
        st = eng.stats().sanitizer
        assert st["write_checks"] > 0 and st["verifications"] > 0

    def test_sanitize_requires_paged(self, lm):
        with pytest.raises(ValueError, match="paged"):
            ServeConfig(paged=False, sanitize=True)


# -- retrace watchdog ---------------------------------------------------------


class TestRetraceWatchdog:
    def test_steady_state_decode_compiles_once(self, lm):
        """Pure-decode steady state: run a workload to completion, freeze,
        run the *same-shaped* workload again — every jitted impl must hit
        its compile cache (no trace fires), and no (impl, signature) may
        ever have traced more than once."""
        eng = _engine(lm, prefill_chunk=4)
        wd = RetraceWatchdog.attach(eng)   # before the first step
        sp = SamplingParams(max_tokens=6, ignore_eos=True)

        def pass_once():
            for p in ([1, 2, 3, 4, 5], [6, 7, 8, 9, 10]):
                eng.submit(p, sp)
            for _ in eng.stream():
                pass

        pass_once()                        # warm-up: pays every compile
        wd.check()                         # each signature traced exactly once
        assert all(n == 1 for n in wd.counts.values())
        assert wd.traces_per_impl().get("_decode", 0) >= 1
        wd.freeze()
        pass_once()                        # steady state: zero new traces
        wd.check()

    def test_new_signature_after_freeze_flagged(self, lm):
        eng = _engine(lm, prefill_chunk=4)
        wd = RetraceWatchdog.attach(eng)
        sp = SamplingParams(max_tokens=4, ignore_eos=True)
        eng.submit([1, 2, 3], sp)
        for _ in eng.stream():
            pass
        wd.freeze()
        # a much longer prompt forces a new chunk bucket -> new signature
        eng.submit(list(range(1, 25)), sp)
        for _ in eng.stream():
            pass
        with pytest.raises(RetraceError, match="freeze"):
            wd.check()
