"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp ref.py oracles
(interpret mode on CPU; same code paths compile for TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.fused_gnn import fused_gnn_layer
from repro.kernels.gat_attention import gat_attention
from repro.kernels.scatter_gather import scatter_gather_aggregate

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand_subgraph(key, c, n, f, dtype, edge_frac=0.2):
    ks = jax.random.split(key, 4)
    h = jax.random.normal(ks[0], (c, n, f)).astype(dtype)
    adj = jax.random.uniform(ks[1], (c, n, n))
    adj = jnp.where(adj < edge_frac, adj, 0.0).astype(jnp.float32)
    k_valid = jax.random.randint(ks[2], (c,), n // 2, n + 1)
    mask = (jnp.arange(n)[None, :] < k_valid[:, None]).astype(jnp.float32)
    adj = adj * mask[:, :, None] * mask[:, None, :]
    h = h * mask[..., None].astype(dtype)
    return h, adj, mask


class TestFusedGNN:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("c,n,f_in,f_out", [
        (1, 8, 16, 16), (2, 64, 128, 256), (3, 128, 512, 256),
        (2, 256, 256, 512), (1, 64, 500, 256),  # unaligned f_in
    ])
    def test_matches_ref(self, c, n, f_in, f_out, dtype):
        key = jax.random.PRNGKey(n * f_in + f_out)
        h, adj, mask = _rand_subgraph(key, c, n, f_in, dtype)
        ks = jax.random.split(key, 3)
        wn = jax.random.normal(ks[0], (f_in, f_out)).astype(dtype) * 0.1
        ws = jax.random.normal(ks[1], (f_in, f_out)).astype(dtype) * 0.1
        b = jax.random.normal(ks[2], (f_out,)).astype(dtype) * 0.1
        for w_self in (None, ws):
            got = fused_gnn_layer(adj, h, wn, w_self, b, mask, act="relu",
                                  interpret=True)
            want = ref.fused_gnn_layer_ref(adj, h, wn, w_self, b, mask,
                                           act="relu")
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                **TOL[dtype])

    def test_self_only_is_plain_matmul(self):
        """W_self-only = dense FT kernel (GIN layer 2 path)."""
        key = jax.random.PRNGKey(0)
        h, adj, mask = _rand_subgraph(key, 2, 32, 64, jnp.float32)
        ws = jax.random.normal(key, (64, 128)) * 0.1
        got = fused_gnn_layer(adj, h, None, ws, None, mask, act="none",
                              interpret=True)
        want = jnp.einsum("cnf,fg->cng", h, ws) * mask[..., None]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("block_f", [128, 256])
    def test_block_width_invariance(self, block_f):
        key = jax.random.PRNGKey(3)
        h, adj, mask = _rand_subgraph(key, 2, 64, 128, jnp.float32)
        w = jax.random.normal(key, (128, 512)) * 0.1
        got = fused_gnn_layer(adj, h, w, None, None, mask,
                              block_f=block_f, interpret=True)
        want = ref.fused_gnn_layer_ref(adj, h, w, None, None, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestScatterGather:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("c,n,f,e", [
        (1, 8, 16, 24), (2, 64, 128, 300), (2, 128, 256, 1000),
        (1, 256, 512, 130),  # e < block
    ])
    def test_matches_ref(self, c, n, f, e, dtype):
        key = jax.random.PRNGKey(e)
        ks = jax.random.split(key, 4)
        src = jax.random.randint(ks[0], (c, e), 0, n).astype(jnp.int32)
        dst = jax.random.randint(ks[1], (c, e), 0, n).astype(jnp.int32)
        w = jax.random.normal(ks[2], (c, e))
        # zero out a padding tail like real batches have
        w = jnp.where(jnp.arange(e)[None, :] < e - 7, w, 0.0)
        h = jax.random.normal(ks[3], (c, n, f)).astype(dtype)
        got = scatter_gather_aggregate(src, dst, w, h, interpret=True)
        want = ref.scatter_gather_aggregate_ref(src, dst, w, h)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_accumulation_raw_hazard(self):
        """Many edges hitting ONE destination accumulate exactly (the
        paper's RAW-hazard case, resolved here by matmul reduction)."""
        c, n, f, e = 1, 16, 32, 64
        src = jnp.zeros((c, e), jnp.int32)
        dst = jnp.full((c, e), 3, jnp.int32)
        w = jnp.ones((c, e))
        h = jnp.ones((c, n, f))
        got = scatter_gather_aggregate(src, dst, w, h, interpret=True)
        assert float(got[0, 3, 0]) == e
        assert float(jnp.abs(got[0, :3]).sum()) == 0.0


class TestGATAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("c,n,f,heads", [
        (1, 8, 16, 1), (2, 64, 256, 4), (2, 128, 256, 8), (1, 256, 512, 4),
    ])
    def test_matches_ref(self, c, n, f, heads, dtype):
        key = jax.random.PRNGKey(n + heads)
        ks = jax.random.split(key, 4)
        z = jax.random.normal(ks[0], (c, n, f)).astype(dtype)
        s_src = jax.random.normal(ks[1], (c, n, heads))
        s_dst = jax.random.normal(ks[2], (c, n, heads))
        struct = (jax.random.uniform(ks[3], (c, n, n)) < 0.3).astype(
            jnp.float32)
        struct = struct + jnp.eye(n)[None]           # self loops
        got = gat_attention(z, s_src, s_dst, struct, n_heads=heads,
                            interpret=True)
        want = ref.gat_attention_ref(z, s_src, s_dst, struct,
                                     n_heads=heads)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_rows_sum_to_one(self):
        """Attention over each destination's in-neighborhood is a proper
        distribution: aggregating constant features returns the constant."""
        c, n, f = 1, 32, 64
        z = jnp.ones((c, n, f))
        s_src = jnp.zeros((c, n, 1))
        s_dst = jnp.zeros((c, n, 1))
        struct = jnp.ones((c, n, n))
        got = gat_attention(z, s_src, s_dst, struct, n_heads=1,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(got), 1.0, rtol=1e-5)


class TestBackendDispatch:
    @pytest.mark.parametrize("backend,interpret",
                             [("tpu", False), ("cpu", True)])
    def test_compiles_on_tpu_interprets_on_cpu(self, monkeypatch, backend,
                                               interpret):
        from repro.kernels import ops
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert ops._interpret() is interpret

    def test_other_backends_refuse(self, monkeypatch):
        """No silent interpret fallback: a GPU (or any other) backend
        must pick impl='xla' explicitly."""
        from repro.kernels import ops
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="impl='xla'"):
            ops._interpret()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("b,h,sq,sk,d,bq,bk", [
        (1, 2, 64, 64, 32, 32, 32),
        (2, 1, 128, 128, 64, 64, 32),
        (1, 2, 64, 128, 32, 32, 64),   # cross lengths (non-causal only)
    ])
    def test_matches_softmax_ref(self, b, h, sq, sk, d, bq, bk, causal):
        from repro.kernels.flash_attention import flash_attention
        if causal and sq != sk:
            pytest.skip("causal requires square")
        key = jax.random.PRNGKey(sq + sk)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, h, sq, d))
        k = jax.random.normal(ks[1], (b, h, sk, d))
        v = jax.random.normal(ks[2], (b, h, sk, d))
        got = flash_attention(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
        if causal:
            mask = jnp.tril(jnp.ones((sq, sk), bool))
            s = jnp.where(mask, s, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        from repro.kernels.flash_attention import flash_attention
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 2, 64, 32)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 64, 32)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 64, 32)).astype(jnp.bfloat16)
        got = flash_attention(q, k, v, block_q=32, block_k=32,
                              interpret=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / (32 ** 0.5)
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -1e30)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                          v.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=3e-2, atol=3e-2)
