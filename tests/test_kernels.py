"""Per-kernel validation: shape/dtype sweeps vs the ref.py jnp oracles
(on the CPU, interpret=None runs the Pallas kernel bodies in the interpreter)."""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_eneac import HotspotConfig
from repro.kernels.flash_attention.ops import flash_attention, kernel_hbm_bytes
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.hotspot.ops import (
    band_window,
    hotspot,
    hotspot_hpc_window,
    hotspot_rows_host,
)
from repro.kernels.hotspot.ref import hotspot_ref
from repro.kernels.platform import (
    VMEM_CAP_BYTES,
    VMEM_DEFAULT_BYTES,
    resolve_interpret,
    vmem_limit,
)
from repro.kernels.spmm.csr import (
    csr_window_start,
    csr_window_tiles,
    spmm_csr_window,
)
from repro.kernels.spmm.ops import (
    CsrWindowOp,
    HostCsr,
    make_hybrid_executor,
    pad_rhs,
    spmm_cc,
    spmm_rows_host,
    spmm_window_start,
)
from repro.kernels.spmm.ref import (
    CsrProblem,
    make_csr_problem,
    make_problem,
    spmm_dense_ref,
    spmm_csr_ref,
    spmm_ell_ref,
    to_block_ell,
)
from repro.kernels.spmm.spmm import BlockEllArrays, spmm_block_ell_pallas

KEY = jax.random.PRNGKey(0)


class TestHotspot:
    @pytest.mark.parametrize("grid,steps", [(32, 1), (64, 4), (128, 2)])
    @pytest.mark.parametrize("mode", ["hp", "hpc"])
    def test_kernel_matches_oracle(self, grid, steps, mode):
        cfg = HotspotConfig(grid=grid, iterations=grid)
        t0 = 80.0 + 10 * jax.random.uniform(KEY, (grid, grid))
        p = jax.random.uniform(jax.random.PRNGKey(1), (grid, grid))
        ref = hotspot_ref(t0, p, cfg, steps)
        out = hotspot(t0, p, cfg, steps, mode=mode)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    def test_cc_is_oracle(self):
        cfg = HotspotConfig(grid=32, iterations=32)
        t0 = jnp.full((32, 32), 80.0)
        p = jnp.zeros((32, 32))
        out = hotspot(t0, p, cfg, 3, mode="cc")
        ref = hotspot_ref(t0, p, cfg, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


class TestHotspotBands:
    """The scheduler's unit of work: a row band, evolved exactly."""

    GRID, STEPS, CHUNK = 96, 3, 16

    def _problem(self, grid=GRID, steps=STEPS):
        cfg = HotspotConfig(grid=grid, iterations=grid)
        t0 = 80.0 + 10 * jax.random.uniform(KEY, (grid, grid))
        p = jax.random.uniform(jax.random.PRNGKey(1), (grid, grid))
        ref = np.asarray(hotspot_ref(t0, p, cfg, steps))
        return cfg, np.asarray(t0), np.asarray(p), ref

    @pytest.mark.parametrize("start,stop", [(0, 16), (1, 17), (40, 56),
                                            (88, 96), (90, 96)])
    def test_fixed_window_kernel_is_exact_for_any_band(self, start, stop):
        # the last partial chunk (90, 96) runs the same window shape
        cfg, t0, p, ref = self._problem()
        window = self.CHUNK + 2 * self.STEPS
        lo = band_window(start, stop, self.GRID, window, self.STEPS)
        out = hotspot_hpc_window(jnp.asarray(t0), jnp.asarray(p), np.int32(lo),
                                 cfg=cfg, window=window, steps=self.STEPS)
        assert out.shape == (window, self.GRID)
        np.testing.assert_allclose(np.asarray(out)[start - lo:stop - lo],
                                   ref[start:stop], rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("start,stop,grid,steps", [
        pytest.param(0, 5, GRID, STEPS, id="0-5"),
        pytest.param(30, 61, GRID, STEPS, id="30-61"),
        pytest.param(95, 96, GRID, STEPS, id="95-96"),
        # the halo reaches row 0 in the first step only
        pytest.param(2, 18, GRID, STEPS, id="top-edge"),
        pytest.param(78, 94, GRID, STEPS, id="bottom-edge"),
        pytest.param(50, 51, GRID, STEPS, id="single-interior-row"),
        pytest.param(0, GRID, GRID, STEPS, id="whole-grid"),
        # 8 halo rows each side of a 16-row band overrun a 24-row grid
        pytest.param(4, 20, 24, 8, id="halo-past-both-edges"),
    ])
    def test_host_rows_match_the_oracle(self, start, stop, grid, steps):
        cfg, t0, p, ref = self._problem(grid, steps)
        out = hotspot_rows_host(t0, p, start, stop, cfg, steps)
        assert out.shape == (stop - start, grid) and out.dtype == np.float32
        np.testing.assert_allclose(out, ref[start:stop], rtol=1e-5, atol=1e-4)

    def test_host_rows_on_four_threads_match_serial_bitwise(self):
        # the CC units' contract: four threads at once on views of one
        # grid, which the op only reads and whose scratch is its own
        grid, steps = 512, 8
        cfg = HotspotConfig(grid=grid, iterations=grid)
        rng = np.random.default_rng(0)
        t0 = (80.0 + 10.0 * rng.random((grid, grid))).astype(np.float32)
        p = rng.random((grid, grid), dtype=np.float32)
        t0.setflags(write=False)
        p.setflags(write=False)
        # bands of one height, so a scratch buffer kept between calls
        # would be one shape, and shared
        bands = [(16, 32), (160, 176), (300, 316), (480, 496)]
        serial = [hotspot_rows_host(t0, p, a, b, cfg, steps) for a, b in bands]
        gate = threading.Barrier(len(bands))
        results = {}

        def work(i, a, b):
            gate.wait(timeout=30)
            results[i] = [hotspot_rows_host(t0, p, a, b, cfg, steps)
                          for _ in range(20)]

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(i, a, b))
                       for i, (a, b) in enumerate(bands)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(th.is_alive() for th in threads)
        assert sorted(results) == list(range(len(bands)))
        for i, outs in results.items():
            for out in outs:
                np.testing.assert_array_equal(out, serial[i])

    def test_band_window_slides_instead_of_shrinking(self):
        assert band_window(0, 16, 96, 22, 3) == 0
        assert band_window(40, 56, 96, 22, 3) == 37
        assert band_window(90, 96, 96, 22, 3) == 74
        with pytest.raises(ValueError, match="does not fit"):
            band_window(0, 17, 96, 22, 3)


class TestPlatform:
    def test_interpret_only_off_the_tpu(self, monkeypatch):
        assert resolve_interpret(None) is True      # the CPU test backend
        assert resolve_interpret(False) is False    # lowering for a described TPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_interpret(None) is False
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            resolve_interpret(True)

    def test_vmem_limit_bounds(self):
        assert vmem_limit(1024, what="x") == VMEM_DEFAULT_BYTES
        assert vmem_limit(80 * 2**20, what="x") == 80 * 2**20
        with pytest.raises(ValueError, match="the big operand"):
            vmem_limit(VMEM_CAP_BYTES + 1, what="the big operand")


class TestSpmm:
    @pytest.mark.parametrize("rows,cols,n", [(40, 256, 16), (64, 384, 32),
                                             (17, 128, 8)])
    @pytest.mark.parametrize("nnz_mean", [2.0, 8.0])
    def test_block_ell_kernel_matches_dense_oracle(self, rows, cols, n, nnz_mean):
        p = make_problem(rows, cols, n, nnz_mean=nnz_mean, seed=rows + n)
        ref = spmm_dense_ref(p)
        be = to_block_ell(p)
        out = spmm_block_ell_pallas(BlockEllArrays(be), jnp.asarray(pad_rhs(p)))
        np.testing.assert_allclose(np.asarray(out[:rows, :n]), ref,
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("start,stop", [(0, 64), (5, 69), (200, 260),
                                            (290, 300)])
    def test_window_at_an_offset_matches_dense_oracle(self, start, stop):
        p = make_problem(300, 700, 10, nnz_mean=12.0, seed=3)
        ref = spmm_dense_ref(p)
        be = to_block_ell(p)
        window = 64 // 8 + 1
        rb0 = spmm_window_start(start, stop, be.n_row_blocks, window)
        out = spmm_block_ell_pallas(BlockEllArrays(be), jnp.asarray(pad_rhs(p)),
                                    np.int32(rb0), n_row_blocks=window)
        assert out.shape == (window * 8, 128)   # N padded to a lane width
        off = rb0 * 8
        np.testing.assert_allclose(np.asarray(out)[start - off:stop - off, :10],
                                   ref[start:stop], rtol=1e-4, atol=1e-4)

    def test_window_start_slides_back_at_the_end(self):
        assert spmm_window_start(200, 260, 38, 9) == 25
        assert spmm_window_start(290, 300, 38, 9) == 29
        with pytest.raises(ValueError, match="do not fit"):
            spmm_window_start(0, 80, 38, 9)

    def test_host_csr_path_matches_dense_oracle(self):
        p = make_problem(50, 300, 6, nnz_mean=5.0, seed=11)
        ref = spmm_dense_ref(p)
        csr = HostCsr.from_ell(p)
        for start, stop in [(0, 50), (7, 8), (20, 44)]:
            np.testing.assert_allclose(
                spmm_rows_host(csr, p.rhs, start, stop), ref[start:stop],
                rtol=1e-4, atol=1e-4)

    def test_gather_path_matches_dense_oracle(self):
        p = make_problem(32, 128, 8, nnz_mean=4.0, seed=7)
        ref = spmm_dense_ref(p)
        out = spmm_cc(jnp.asarray(p.vals), jnp.asarray(p.cols), jnp.asarray(p.rhs))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)

    def test_hybrid_executor_exact_any_split(self):
        p = make_problem(48, 256, 16, nnz_mean=6.0, seed=3)
        ref = spmm_dense_ref(p)
        ex, order = make_hybrid_executor(p)
        inv = np.empty_like(order)
        inv[order] = np.arange(len(order))
        from repro.core.parallel_for import SplitDecision
        for nd in (0, 16, 32, 48):
            res, _ = ex.run(SplitDecision(n_dense=nd, n_sparse=48 - nd,
                                          predicted_time=0.0))
            np.testing.assert_allclose(np.asarray(res)[inv], ref,
                                       rtol=1e-4, atol=1e-4)


def _without_rows(p: CsrProblem, a: int, b: int) -> CsrProblem:
    """``p`` with the entries of rows [a, b) dropped."""
    ia, ib = p.indptr[a], p.indptr[b]
    indptr = p.indptr.copy()
    indptr[a:b + 1] = ia
    indptr[b + 1:] -= ib - ia
    keep = np.r_[0:ia, ib:p.nnz]
    return CsrProblem(indptr, p.indices[keep], p.data[keep], p.rhs)


# a SCALE 8 graph (256 rows, longest row 151) with rows 128..191 emptied
CSR = _without_rows(make_csr_problem(8, 12, seed=5), 128, 192)
CSR_REF = np.asarray(spmm_csr_ref(CSR))
LONGEST = int(np.argmax(np.diff(CSR.indptr)))


class TestSpmmCsr:
    @pytest.mark.parametrize("start,stop,window,tile,block", [
        (0, 64, 64, 64, 16),                          # window at the start
        (96, 160, 64, 64, 16),                        # middle, half empty
        (240, 256, 64, 64, 16),                       # slid back at the end
        (LONGEST, LONGEST + 8, 32, 32, 8),            # a row longer than a tile
        (128, 192, 64, 64, 16),                       # only empty rows
        (30, 80, 50, 24, 16),                         # W not a multiple of tile or block
        (0, 256, 256, 4096, 128),                     # one tile over all
    ], ids=["start", "middle", "end", "long-row", "empty", "ragged", "whole"])
    def test_window_matches_reference(self, start, stop, window, tile, block):
        assert np.diff(CSR.indptr).max() > 32
        lo = csr_window_start(start, stop, CSR.rows, window)
        out = spmm_csr_window(jnp.asarray(CSR.indptr, jnp.int32), jnp.asarray(CSR.indices),
                              jnp.asarray(CSR.data), jnp.asarray(CSR.rhs), np.int32(lo),
                              window=window, tile=tile, block=block)
        assert out.shape == (window, CSR.rhs.shape[1])
        np.testing.assert_allclose(np.asarray(out), CSR_REF[lo:lo + window],
                                   rtol=1e-5, atol=1e-5)

    def test_window_start_slides_back_at_the_end(self):
        assert csr_window_start(200, 230, 256, 64) == 192
        assert csr_window_start(10, 40, 256, 64) == 10
        with pytest.raises(ValueError, match="do not fit"):
            csr_window_start(0, 80, 256, 64)

    def test_window_tiles_follow_entries(self):
        ip = CSR.indptr
        assert csr_window_tiles(ip, 128, 64, tile=64) == 0                    # only empty rows
        longest = int(ip[LONGEST + 1] - ip[LONGEST])
        assert csr_window_tiles(ip, LONGEST, 1, tile=32) == -(-longest // 32)
        assert csr_window_tiles(ip, 0, 64, tile=1 << 20) == 1
        assert csr_window_tiles(ip, 0, 64, tile=8) == -(-int(ip[64] - ip[0]) // 8)

    def test_op_pads_lanes_counts_entries_and_reuses_one_program(self):
        from repro.core import trace

        op = CsrWindowOp(HostCsr(CSR.indptr, CSR.indices, CSR.data), CSR.rhs,
                         jax.devices()[0], window=64)
        trace.open_work()
        lo, out = op(240, 256)
        lo2, out2 = op(0, 50)
        assert trace.close_work() == CSR.indptr[256] - CSR.indptr[240] + CSR.indptr[50]
        assert (lo, lo2) == (192, 0) and out.shape == (64, 128)
        np.testing.assert_allclose(np.asarray(out)[:, :12], CSR_REF[192:256], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out)[:, 12:], 0.0)
        np.testing.assert_allclose(np.asarray(out2)[:, :12], CSR_REF[:64], rtol=1e-5, atol=1e-5)

    def test_host_rows_on_csr_arrays(self):
        csr = HostCsr(CSR.indptr, CSR.indices, CSR.data)
        for start, stop in [(0, 256), (LONGEST, LONGEST + 1), (128, 192), (100, 140)]:
            np.testing.assert_allclose(spmm_rows_host(csr, CSR.rhs, start, stop),
                                       CSR_REF[start:stop], rtol=1e-5, atol=1e-5)

    def test_reference_matches_the_dense_oracle(self):
        p = make_problem(40, 90, 6, nnz_mean=5.0, seed=2)
        csr = HostCsr.from_ell(p)
        q = CsrProblem(csr.indptr, csr.indices, csr.data, p.rhs)
        np.testing.assert_allclose(np.asarray(spmm_csr_ref(q)), spmm_dense_ref(p),
                                   rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,sq,h,kvh,d,causal,window",
        [
            (2, 128, 4, 2, 32, True, 0),
            (1, 256, 8, 1, 16, True, 0),     # MQA
            (2, 128, 4, 4, 64, False, 0),    # MHA non-causal
            (1, 256, 4, 2, 32, True, 64),    # local window
            (1, 128, 2, 2, 128, True, 0),    # wide head
        ],
    )
    def test_matches_oracle(self, b, sq, h, kvh, d, causal, window):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, sq, kvh, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, sq, kvh, d), jnp.float32)
        ref = mha_ref(q, k, v, causal=causal, window=window)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              q_block=64, kv_block=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 32)).astype(dtype)
        k = jax.random.normal(ks[1], (1, 128, 2, 32)).astype(dtype)
        v = jax.random.normal(ks[2], (1, 128, 2, 32)).astype(dtype)
        ref = mha_ref(q, k, v)
        out = flash_attention(q, k, v, q_block=64, kv_block=64)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol)

    def test_traffic_model_is_qkvo_linear(self):
        fwd = kernel_hbm_bytes(1, 4096, 4096, 32, 8, 128)
        # Q+O = 2·S·H·D·2, K+V = 2·S·KVH·D·2
        expect = 2 * (4096 * 32 * 128 * 2) + 2 * (4096 * 8 * 128 * 2)
        assert fwd == expect
