"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant as Q
from repro.core.distill import attention_relation_loss
from repro.kernels.bitlinear import ops as bl_ops, ref as bl_ref
from repro.kernels.bitlinear.kernel import bitlinear_kernel
from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref
from repro.kernels.paged_prefill import (kernel as pp_kernel, ops as pp_ops,
                                        ref as pp_ref)
from repro.kernels.relation_kd import ops as rk_ops, ref as rk_ref
from repro.kernels.relation_kd.kernel import relation_kl_rows_kernel
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
from repro.kernels.w2a8_gemv import ops as w2_ops, ref as w2_ref
from repro.nn.ssm import ssd_chunked, ssd_sequential


class TestBitLinearKernel:
    @pytest.mark.parametrize("m,k,n", [(8, 64, 32), (256, 512, 256),
                                       (100, 300, 200), (1, 128, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, m, k, n, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.02
        qw, delta = Q.weight_quant_absmean(w)
        gamma = jnp.max(jnp.abs(x.astype(jnp.float32)), -1, keepdims=True)
        y_k = bitlinear_kernel(x, qw.astype(jnp.int8), gamma, delta,
                               bm=128, bn=128, bk=128, interpret=True)
        y_r = bl_ref.bitlinear_ref(x, qw.astype(jnp.int8), gamma, delta)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=tol, atol=tol)

    def test_ops_match_fake_quant_forward(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 64))
        w = jax.random.normal(jax.random.PRNGKey(3), (64, 48)) * 0.02
        y = bl_ops.bitlinear_matmul(x, w)
        y_ref = bl_ref.bitlinear_full_ref(x.reshape(-1, 64), w).reshape(4, 32, 48)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)

    def test_ste_gradients(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (8, 64))
        w = jax.random.normal(jax.random.PRNGKey(5), (64, 32)) * 0.02

        def loss_kernel(x, w):
            return jnp.sum(bl_ops.bitlinear_matmul(x, w) ** 2)

        def loss_jnp(x, w):
            xq = Q.fake_quant_act(x)
            wq = Q.fake_quant_weight(w)
            return jnp.sum((xq @ wq) ** 2)

        gk = jax.grad(loss_kernel, (0, 1))(x, w)
        gj = jax.grad(loss_jnp, (0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gj[1]),
                                   rtol=1e-3, atol=1e-3)


class TestW2A8:
    @pytest.mark.parametrize("m,k,n", [(4, 128, 64), (16, 512, 256),
                                       (2, 256, 100), (1, 1024, 128)])
    def test_matches_ref(self, m, k, n):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.02
        qw, delta = Q.weight_quant_absmean(w)
        wp = Q.pack_ternary(qw.astype(jnp.int8))
        yk = w2_ops.w2a8_matmul(x, wp, delta)
        yr = w2_ref.w2a8_ref(x, wp, delta)
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)

    def test_packed_equals_unpacked_bitlinear(self):
        """decode path (packed kernel) == training fake-quant forward."""
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 256))
        w = jax.random.normal(jax.random.PRNGKey(3), (256, 64)) * 0.02
        qw, delta = Q.weight_quant_absmean(w)
        wp = Q.pack_ternary(qw.astype(jnp.int8))
        y_packed = w2_ops.w2a8_matmul(x, wp, delta)
        y_qat = bl_ref.bitlinear_full_ref(x, w)
        np.testing.assert_allclose(np.asarray(y_packed), np.asarray(y_qat),
                                   rtol=1e-4, atol=1e-4)


class TestRelationKD:
    @pytest.mark.parametrize("bh,l,d", [(2, 64, 32), (4, 100, 16), (3, 256, 64)])
    def test_rows_match_ref(self, bh, l, d):
        s = jax.random.normal(jax.random.PRNGKey(0), (bh, l, d))
        s = s / jnp.linalg.norm(s, axis=-1, keepdims=True)
        t = jax.random.normal(jax.random.PRNGKey(1), (bh, l, d))
        t = t / jnp.linalg.norm(t, axis=-1, keepdims=True)
        rk = relation_kl_rows_kernel(s, t, temp=1.0, bl=32, bj=32, interpret=True)
        rr = rk_ref.relation_kl_rows_ref(s, t, 1.0)
        np.testing.assert_allclose(np.asarray(rk), np.asarray(rr),
                                   rtol=1e-4, atol=1e-5)

    def test_zero_when_identical(self):
        s = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
        s = s / jnp.linalg.norm(s, axis=-1, keepdims=True)
        rk = relation_kl_rows_kernel(s, s, interpret=True)
        np.testing.assert_allclose(np.asarray(rk), 0.0, atol=1e-5)

    def test_loss_and_grad_match_jnp_path(self):
        ss = jax.random.normal(jax.random.PRNGKey(3), (3, 2, 4, 64, 16))
        ts = jax.random.normal(jax.random.PRNGKey(4), (3, 2, 4, 64, 16))
        l_j = attention_relation_loss(ss, ts, split_heads=2)
        l_k = rk_ops.relation_kd_loss(ss, ts, split_heads=2)
        np.testing.assert_allclose(float(l_j), float(l_k), rtol=1e-4)
        g_j = jax.grad(lambda s: attention_relation_loss(s, ts, split_heads=2))(ss)
        g_k = jax.grad(lambda s: rk_ops.relation_kd_loss(s, ts, split_heads=2))(ss)
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_j),
                                   rtol=1e-3, atol=1e-6)


def _paged_case(B, Hq, Hkv, Dh, bs, L, idxs, softcap=0.0, trash_rows=(),
                seed=0):
    """Build a paged decode problem with exclusively-owned blocks per live
    row (mirrors the allocator's no-sharing invariant) and run kernel + ref.

    Returns (kernel outs, ref outs, live row indices)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n_blocks = 1 + B * L                  # trash block + exclusive blocks
    k_pool = jax.random.normal(ks[0], (n_blocks, Hkv, bs, Dh), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_blocks, Hkv, bs, Dh), jnp.float32)
    q = jax.random.normal(ks[2], (B, Hq, Dh), jnp.float32)
    kn = jax.random.normal(ks[3], (B, Hkv, Dh), jnp.float32)
    vn = jax.random.normal(ks[4], (B, Hkv, Dh), jnp.float32)
    bt = np.zeros((B, L), np.int32)       # unallocated entries -> trash (0)
    nxt = 1
    for b in range(B):
        if b in trash_rows:
            continue
        for j in range(min(idxs[b] // bs, L - 1) + 1):
            bt[b, j] = nxt
            nxt += 1
    idx = jnp.asarray(idxs, jnp.int32)
    bt = jnp.asarray(bt)
    got = pa_ops.paged_attention_decode(q, kn, vn, k_pool, v_pool, bt, idx,
                                        softcap=softcap, interpret=True)
    qg = q.reshape(B, Hkv, Hq // Hkv, Dh)
    want = pa_ref.paged_attention_decode_ref(qg, kn, vn, k_pool, v_pool, bt,
                                             idx, 1.0 / (Dh ** 0.5), softcap)
    live = [b for b in range(B) if b not in trash_rows]
    return got, want, live


def _assert_paged_parity(got, want, live, B, Hq, Dh):
    o_k, kp_k, vp_k = got
    o_r, kp_r, vp_r = want
    o_r = np.asarray(o_r).reshape(B, Hq, Dh)
    np.testing.assert_allclose(np.asarray(o_k)[live], o_r[live],
                               rtol=2e-5, atol=2e-5)
    # scatter parity must be exact on every owned block; the trash block
    # (id 0) is excluded — colliding idle-row writes land in unspecified
    # order there, and nothing ever attends it
    np.testing.assert_array_equal(np.asarray(kp_k)[1:], np.asarray(kp_r)[1:])
    np.testing.assert_array_equal(np.asarray(vp_k)[1:], np.asarray(vp_r)[1:])


class TestPagedAttentionDecode:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
    def test_gqa_ratios_mixed_depths(self, hq, hkv):
        B, Dh, bs, L = 3, 32, 4, 4
        got, want, live = _paged_case(B, hq, hkv, Dh, bs, L, [0, 5, 13])
        _assert_paged_parity(got, want, live, B, hq, Dh)

    @pytest.mark.parametrize("idxs", [[4, 7], [3, 8], [0, 1]])
    def test_partial_last_block_and_boundaries(self, idxs):
        """idx on / off block boundaries: the freshly-entered block holds no
        stored tokens, only the fused write; stale slots past idx masked."""
        B, Hq, Hkv, Dh, bs, L = 2, 4, 2, 32, 4, 3
        got, want, live = _paged_case(B, Hq, Hkv, Dh, bs, L, idxs)
        _assert_paged_parity(got, want, live, B, Hq, Dh)

    def test_single_block_tables(self):
        B, Hq, Hkv, Dh, bs, L = 2, 2, 2, 32, 8, 1
        got, want, live = _paged_case(B, Hq, Hkv, Dh, bs, L, [0, 6])
        _assert_paged_parity(got, want, live, B, Hq, Dh)

    def test_idle_trash_block_rows_are_finite(self):
        """Idle rows (table all trash, parked write position) must stream
        garbage without poisoning live rows or producing non-finite output."""
        B, Hq, Hkv, Dh, bs, L = 3, 4, 2, 32, 4, 3
        got, want, live = _paged_case(B, Hq, Hkv, Dh, bs, L, [2, 11, 11],
                                      trash_rows=(2,))
        _assert_paged_parity(got, want, live, B, Hq, Dh)
        assert np.isfinite(np.asarray(got[0])).all()

    def test_logit_softcap(self):
        B, Hq, Hkv, Dh, bs, L = 2, 4, 2, 32, 4, 3
        got, want, live = _paged_case(B, Hq, Hkv, Dh, bs, L, [5, 9],
                                      softcap=30.0)
        _assert_paged_parity(got, want, live, B, Hq, Dh)

    def test_kv_bytes_model_resident_vs_dense(self):
        """The traffic model the benchmark/roofline report: fused reads
        resident blocks (+1 trash fetch per idle row), gather reads the
        dense window for every slot."""
        kw = dict(table_width=8, block_size=8, n_kv_heads=2, head_dim=32,
                  n_layers=2, itemsize=4)
        per_tok = 2 * 2 * 32 * 4 * 2
        positions = [3, 20, 63, 63]          # slot 3 idle (parked)
        fused = pa_ops.decode_kv_bytes(positions, [0, 1, 2], fused=True, **kw)
        dense = pa_ops.decode_kv_bytes(positions, [0, 1, 2], fused=False, **kw)
        assert fused == (1 + 3 + 8 + 1) * 8 * per_tok
        assert dense == 4 * 8 * 8 * per_tok
        assert fused < dense


def _prefill_case(B, Hq, Hkv, Dh, bs, L, T, starts, lens, softcap=0.0,
                  trash_rows=(), seed=0, shared=0, poison=False, table=None):
    """Build a chunked paged-prefill problem with exclusively-owned blocks
    per live row covering [0, start + len) and run kernel + ref.  Rows after
    the first live one read its first ``shared`` blocks in place (a
    trie-shared prefix); ``table`` replaces the built block table.  With
    ``poison`` every pool block the kernel's rule (``ops.fetched_blocks``)
    does not fetch holds NaN on the kernel's side only, so reading one shows
    in its output.

    Returns (kernel outs, ref outs, live row indices)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n_blocks = 1 + B * L                  # trash block + exclusive blocks
    k_pool = jax.random.normal(ks[0], (n_blocks, Hkv, bs, Dh), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_blocks, Hkv, bs, Dh), jnp.float32)
    q = jax.random.normal(ks[2], (B, T, Hq, Dh), jnp.float32)
    kc = jax.random.normal(ks[3], (B, T, Hkv, Dh), jnp.float32)
    vc = jax.random.normal(ks[4], (B, T, Hkv, Dh), jnp.float32)
    bt = np.zeros((B, L), np.int32)       # unallocated entries -> trash (0)
    nxt = 1
    live = [b for b in range(B) if b not in trash_rows]
    for b in live:
        last = min((starts[b] + lens[b] - 1) // bs, L - 1)
        for j in range(last + 1):
            if b != live[0] and j < shared:
                bt[b, j] = bt[live[0], j]
            else:
                bt[b, j] = nxt
                nxt += 1
    if table is not None:
        bt = np.asarray(table, np.int32)
    start = jnp.asarray(starts, jnp.int32)
    ln = jnp.asarray(lens, jnp.int32)
    kk, kv = k_pool, v_pool
    if poison:
        fetched = np.zeros(n_blocks, bool)
        for mask in pp_ops.fetched_blocks(starts, lens, bt, bs):
            fetched[bt[mask]] = True
        kk = jnp.where(fetched[:, None, None, None], k_pool, jnp.nan)
        kv = jnp.where(fetched[:, None, None, None], v_pool, jnp.nan)
    bt = jnp.asarray(bt)
    got = pp_ops.paged_prefill_chunk(q, kc, vc, kk, kv, bt, start, ln,
                                     softcap=softcap, interpret=True)
    if poison:
        # blocks the rule does not fetch are not written either (trash
        # aside, where colliding discards may land)
        for out in got[1:]:
            assert np.isnan(np.asarray(out)[1:][~fetched[1:]]).all()
        got = (got[0],) + tuple(jnp.where(fetched[:, None, None, None], o, p)
                                for o, p in zip(got[1:], (k_pool, v_pool)))
    g = Hq // Hkv
    qg = (q.reshape(B, T, Hkv, g, Dh).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, T * g, Dh))
    want = pp_ref.paged_prefill_chunk_ref(
        qg, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3), k_pool,
        v_pool, bt, start, ln, 1.0 / (Dh ** 0.5), softcap)
    return got, want, live


def _assert_prefill_parity(got, want, live, lens):
    o_k, kp_k, vp_k = got
    o_r, kp_r, vp_r = want
    o_k = np.asarray(o_k)                       # [B, T, Hq, Dh]
    B, T, Hq, Dh = o_k.shape
    Hkv = np.asarray(kp_r).shape[1]
    g = Hq // Hkv
    o_r = (np.asarray(o_r).reshape(B, Hkv, T, g, Dh)
           .transpose(0, 2, 1, 3, 4).reshape(B, T, Hq, Dh))
    # ctx parity on every valid chunk position of every live row; pad rows
    # (j >= lens) are unnormalized garbage both sides discard
    for b in live:
        np.testing.assert_allclose(o_k[b, :lens[b]], o_r[b, :lens[b]],
                                   rtol=2e-5, atol=2e-5)
    # scatter parity must be exact on every owned block; the trash block
    # (id 0) is excluded — colliding pad/idle writes land in unspecified
    # order there, and nothing ever attends it
    np.testing.assert_array_equal(np.asarray(kp_k)[1:], np.asarray(kp_r)[1:])
    np.testing.assert_array_equal(np.asarray(vp_k)[1:], np.asarray(vp_r)[1:])


class TestPagedPrefillChunk:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
    def test_gqa_ratios_mixed_progress(self, hq, hkv):
        """Rows at different prefill depths: cold start (no resident KV),
        mid-prompt, and a decode row (lens == 1) in one grid."""
        B, Dh, bs, L, T = 3, 32, 4, 6, 8
        got, want, live = _prefill_case(B, hq, hkv, Dh, bs, L, T,
                                        [0, 9, 13], [8, 5, 1])
        _assert_prefill_parity(got, want, live, [8, 5, 1])

    @pytest.mark.parametrize("starts,lens", [([3, 4], [4, 4]), ([7, 2], [2, 3]),
                                             ([0, 5], [4, 2])])
    def test_chunk_straddles_block_boundaries(self, starts, lens):
        """Chunks that start mid-block, end mid-block, or span two blocks:
        the splice must keep resident rows of shared boundary blocks."""
        B, Hq, Hkv, Dh, bs, L, T = 2, 4, 2, 32, 4, 4, 4
        got, want, live = _prefill_case(B, Hq, Hkv, Dh, bs, L, T, starts,
                                        lens)
        _assert_prefill_parity(got, want, live, lens)

    def test_padded_chunk_rows_never_written(self):
        """lens < T: pad positions produce no pool writes (owned blocks hold
        exactly lens new rows) and valid rows are unaffected."""
        B, Hq, Hkv, Dh, bs, L, T = 2, 4, 2, 32, 4, 4, 8
        got, want, live = _prefill_case(B, Hq, Hkv, Dh, bs, L, T, [0, 6],
                                        [3, 5])
        _assert_prefill_parity(got, want, live, [3, 5])

    def test_decode_equivalence_t1(self):
        """T=1 chunks are decode steps: parity with the decode kernel's
        semantics through the same ref."""
        B, Hq, Hkv, Dh, bs, L, T = 2, 4, 2, 32, 4, 3, 1
        got, want, live = _prefill_case(B, Hq, Hkv, Dh, bs, L, T, [5, 8],
                                        [1, 1])
        _assert_prefill_parity(got, want, live, [1, 1])

    def test_idle_trash_block_rows_are_finite(self):
        """Idle rows (table all trash, parked start) stream garbage without
        poisoning live rows or producing non-finite output."""
        B, Hq, Hkv, Dh, bs, L, T = 3, 4, 2, 32, 4, 4, 4
        got, want, live = _prefill_case(B, Hq, Hkv, Dh, bs, L, T,
                                        [2, 11, 11], [4, 1, 1],
                                        trash_rows=(2,))
        _assert_prefill_parity(got, want, live, [4, 1, 1])
        assert np.isfinite(np.asarray(got[0])[live]).all()

    def test_logit_softcap(self):
        B, Hq, Hkv, Dh, bs, L, T = 2, 4, 2, 32, 4, 4, 4
        got, want, live = _prefill_case(B, Hq, Hkv, Dh, bs, L, T, [5, 0],
                                        [4, 4], softcap=30.0)
        _assert_prefill_parity(got, want, live, [4, 4])

    def test_kv_bytes_model_resident_vs_dense(self):
        """The traffic model the benchmark/roofline report: fused reads the
        blocks the kernel's rule fetches (each chunked row's resident blocks
        plus the blocks its chunk is spliced into; an idle row nothing),
        gather reads the dense window for every slot."""
        kw = dict(table_width=8, block_size=8, n_kv_heads=2, head_dim=32,
                  n_layers=2, itemsize=4)
        per_tok = 2 * 2 * 32 * 4 * 2
        starts = [3, 24, 40, 63]             # slot 3 idle (parked)
        lens = [5, 8, 1, 1]                  # two chunks + one decode row
        fused = pp_ops.prefill_kv_bytes(starts, lens, [0, 1, 2], fused=True,
                                        **kw)
        dense = pp_ops.prefill_kv_bytes(starts, lens, [0, 1, 2], fused=False,
                                        **kw)
        # resident ceil(start/bs) + splice blocks: (1+1) + (3+1) + (5+1) + 0
        assert fused == (2 + 4 + 6 + 0) * 8 * per_tok
        assert dense == 4 * 8 * 8 * per_tok
        assert fused < dense

    # (bs, L, T, starts, lens, trash_rows, shared): bs 16 gives P = 8 blocks
    # per tile (128 positions), or the table width where it is narrower
    RULE_CASES = {
        # idle rows parked at max_len - 1 with all-trash tables
        "idle_rows_parked": (16, 4, 8, [20, 4095, 5, 4095], [8, 1, 3, 1],
                             (1, 3), 0),
        # resident prefix ending mid-tile (and mid-block) of a second tile
        "prefix_ends_mid_tile": (16, 16, 8, [168, 50], [8, 1], (), 0),
        # table widths below P
        "width_1": (16, 1, 8, [3, 0], [8, 5], (), 0),
        "width_2": (16, 2, 8, [20, 9], [8, 1], (), 0),
        "width_4": (16, 4, 8, [40, 63], [8, 1], (), 0),
        # chunk crossing a tile boundary (blocks 7 -> 8)
        "chunk_crosses_tile": (16, 16, 8, [124, 128], [8, 8], (), 0),
        # three rows reading a trie-shared 3-block prefix in place
        "prefix_shared": (16, 8, 8, [53, 48, 60], [4, 8, 1], (), 3),
    }

    @pytest.mark.parametrize("dh", [128, 64])
    @pytest.mark.parametrize("case", sorted(RULE_CASES))
    def test_fetch_rule_cases(self, case, dh):
        """Each case against the reference, with every block the kernel's
        rule does not fetch poisoned: ``Dh`` 128 runs the tiled manual-DMA
        kernel, 64 the BlockSpec walk."""
        bs, L, T, starts, lens, trash_rows, shared = self.RULE_CASES[case]
        got, want, live = _prefill_case(len(starts), 4, 2, dh, bs, L, T,
                                        starts, lens, trash_rows=trash_rows,
                                        shared=shared, poison=True)
        _assert_prefill_parity(got, want, live, lens)
        assert np.isfinite(np.asarray(got[0])).all()

    def test_fetch_rule_counts_a_hand_built_plan(self):
        """The host count behind ``StepWork.kv_blocks_scored``: an idle row
        fetches nothing; a row with 43 resident blocks fetches them plus
        the blocks its chunk is spliced into; a pad row whose write block
        is trash fetches only its resident blocks.  The kernel, run on the
        same plan with every other block poisoned, reads no other block."""
        bs, width, t = 16, 128, 32
        starts = [4095, 688, 690, 112]
        lens = [1, 1, 32, 1]
        table = np.zeros((4, width), np.int32)
        nxt = 1
        for b, n in ((1, 44), (2, 46), (3, 7)):    # row 3 stops at block 6
            table[b, :n] = np.arange(nxt, nxt + n)
            nxt += n
        resident, splice = pp_ops.fetched_blocks(starts, lens, table, bs)
        assert resident.sum(1).tolist() == [0, 43, 44, 7]
        assert splice.sum(1).tolist() == [0, 1, 3, 0]
        np.testing.assert_array_equal(
            np.asarray(pp_kernel.resident_blocks(
                jnp.asarray(starts), jnp.asarray(table), bs)),
            resident.sum(1))
        # the kernel on the same plan, every other block poisoned.  Row 3's
        # write is a discard to trash, where the reference's pad writes
        # collide with it: its output is left out, its pool blocks are not
        got, want, _ = _prefill_case(4, 1, 1, 128, bs, width, t, starts,
                                     lens, poison=True, table=table)
        _assert_prefill_parity(got, want, [1, 2], lens)


class TestSSDScan:
    @pytest.mark.parametrize("b,s,h,p,n", [(2, 64, 3, 16, 8), (1, 128, 2, 32, 16)])
    def test_kernel_matches_sequential(self, b, s, h, p, n):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (b, s, h, p))
        a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(1), (b, s, h))) * 0.9 + 0.05
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(2), (b, s, h)))
        B = jax.random.normal(jax.random.PRNGKey(3), (b, s, n))
        C = jax.random.normal(jax.random.PRNGKey(4), (b, s, n))
        y_seq, _ = ssd_sequential(x, a, dt, B, C)
        y_k = ssd_ops.ssd_scan(x, a, dt, B, C, chunk=16)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq),
                                   rtol=1e-4, atol=1e-4)

    def test_chunked_matches_sequential(self):
        key = jax.random.PRNGKey(5)
        b, s, h, p, n = 2, 96, 2, 8, 4
        x = jax.random.normal(key, (b, s, h, p))
        a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(6), (b, s, h)))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(7), (b, s, h)))
        B = jax.random.normal(jax.random.PRNGKey(8), (b, s, n))
        C = jax.random.normal(jax.random.PRNGKey(9), (b, s, n))
        y1, h1 = ssd_sequential(x, a, dt, B, C)
        y2, h2 = ssd_chunked(x, a, dt, B, C, chunk=32)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h1), rtol=1e-4, atol=1e-4)

    def test_custom_vjp(self):
        b, s, h, p, n = 1, 32, 2, 8, 4
        x = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, p))
        a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(1), (b, s, h)))
        dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(2), (b, s, h)))
        B = jax.random.normal(jax.random.PRNGKey(3), (b, s, n))
        C = jax.random.normal(jax.random.PRNGKey(4), (b, s, n))
        gk = jax.grad(lambda x: jnp.sum(ssd_ops.ssd_scan(x, a, dt, B, C, 16) ** 2))(x)
        gs = jax.grad(lambda x: jnp.sum(ssd_sequential(x, a, dt, B, C)[0] ** 2))(x)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gs),
                                   rtol=1e-3, atol=1e-3)
