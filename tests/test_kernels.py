"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.streaming_matmul import streaming_matmul
from repro.models.ssm import ssd_reference_recurrent


class TestStreamingMatmul:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-3), (jnp.bfloat16, 0.5)])
    @pytest.mark.parametrize("M,K,N", [
        (128, 256, 128), (256, 512, 256), (128, 1024, 384), (384, 256, 512),
    ])
    def test_matches_oracle(self, M, K, N, dtype, tol):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (M, K), jnp.float32).astype(dtype)
        w = jax.random.normal(ks[1], (K, N), jnp.float32).astype(dtype)
        got = streaming_matmul(x, w, block_m=128, block_n=128, block_k=128,
                               interpret=True)
        want = ref.matmul_ref(x, w)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32),
            atol=tol, rtol=tol,
        )

    def test_single_k_block(self):
        """Degenerate case: no prefetch step (n_k == 1)."""
        x = jnp.ones((128, 128))
        w = jnp.eye(128)
        got = streaming_matmul(x, w, block_m=128, block_n=128, block_k=128,
                               interpret=True)
        np.testing.assert_allclose(got, x, atol=1e-6)


class TestFlashKernel:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
    @pytest.mark.parametrize("B,H,KV,Sq,Sk,D,Dv,causal,window", [
        (1, 4, 2, 128, 128, 32, 32, True, None),
        (2, 4, 1, 128, 128, 32, 16, True, 64),    # MQA + SWA + MLA-dv
        (1, 2, 2, 128, 256, 32, 32, False, None), # cross attention
        (1, 8, 4, 256, 256, 64, 64, True, None),
    ])
    def test_matches_oracle(self, B, H, KV, Sq, Sk, D, Dv, causal, window,
                            dtype, tol):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, H, Sq, D), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (B, KV, Sk, D), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (B, KV, Sk, Dv), jnp.float32).astype(dtype)
        got = flash_attention_tpu(q, k, v, causal=causal, window=window,
                                  block_q=64, block_k=64, interpret=True)
        want = ref.flash_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32),
            atol=tol, rtol=tol,
        )


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
    @pytest.mark.parametrize("S,n_valid", [
        (32, [1, 7, 32, 20]),
        (1024, [1, 513, 1024, 300]),   # two blocks of 512 positions
    ])
    def test_matches_sdpa_on_the_layer(self, S, n_valid, dtype, tol):
        """Each layer of the stacks, read in place, against the jnp
        attention of the decode step on that layer's slice."""
        from repro.configs import get_config, reduced_config
        from repro.models.layers import _sdpa

        L, B, KV, G, D = 2, 4, 2, 4, 128
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, KV * G, D)).astype(dtype)
        k = jax.random.normal(ks[1], (L, B, S, KV, D)).astype(dtype)
        v = jax.random.normal(ks[2], (L, B, S, KV, D)).astype(dtype)
        n_valid = jnp.array(n_valid)
        mask = (jnp.arange(S)[None, :] < n_valid[:, None])[:, None, None, :]
        cfg = reduced_config(get_config("granite-8b"))
        for layer in range(L):
            got = decode_attention(q, k, v, jnp.int32(layer), n_valid,
                                   interpret=True)
            want = _sdpa(q[:, None], k[layer], v[layer], mask, cfg)[:, 0]
            np.testing.assert_allclose(
                got.astype(jnp.float32), want.astype(jnp.float32),
                atol=tol, rtol=tol,
            )


class TestSSDKernel:
    @pytest.mark.parametrize("L,chunk", [(64, 32), (128, 32), (256, 64)])
    @pytest.mark.parametrize("G", [1, 2])
    def test_matches_recurrent_oracle(self, L, chunk, G):
        Bsz, H, P, N = 2, 4, 32, 32
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        xh = jax.random.normal(ks[0], (Bsz, L, H, P))
        Bm = jax.random.normal(ks[1], (Bsz, L, G, N)) * 0.5
        Cm = jax.random.normal(ks[2], (Bsz, L, G, N)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[3], (Bsz, L, H)))
        A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.5)
        got = ops.ssd(xh, Bm, Cm, dt, A, chunk=chunk, interpret=True)
        want = ssd_reference_recurrent(xh, Bm, Cm, dt, A)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_ops_attention_layout_roundtrip():
    """ops.attention matches the models-layer flash (same layout contract)."""
    from repro.models.flash import flash_attention as jnp_flash

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    got = ops.attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = jnp_flash(q, k, v, block_k=64)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


class TestBackendHelper:
    """kernel_backend()/resolve_interpret(): the one platform decision."""

    def test_auto_resolves_by_platform(self, monkeypatch):
        from repro import kernels

        monkeypatch.delenv(kernels.BACKEND_ENV, raising=False)
        backend = kernels.kernel_backend()
        on_tpu = jax.default_backend() == "tpu"
        assert backend == ("pallas" if on_tpu else "interpret")
        assert kernels.resolve_interpret(None) is (not on_tpu)

    @pytest.mark.parametrize("choice,platform,interpret", [
        pytest.param("pallas", "cpu", False, id="pallas-False"),
        pytest.param("pallas", "tpu", False, id="pallas-tpu-False"),
        pytest.param("interpret", "cpu", True, id="interpret-True"),
        # refused: interpreting on a TPU would hide the chip
        pytest.param("interpret", "tpu", None, id="interpret-tpu-refused"),
    ])
    def test_env_override(self, monkeypatch, choice, platform, interpret):
        from repro import kernels

        monkeypatch.setattr(kernels.jax, "default_backend", lambda: platform)
        monkeypatch.setenv(kernels.BACKEND_ENV, choice)
        if interpret is None:
            with pytest.raises(RuntimeError, match="interpret on a TPU"):
                kernels.kernel_backend()
            return
        assert kernels.kernel_backend() == choice
        assert kernels.resolve_interpret(None) is interpret

    def test_explicit_beats_env(self, monkeypatch):
        from repro import kernels

        monkeypatch.setenv(kernels.BACKEND_ENV, "pallas")
        assert kernels.resolve_interpret(True) is True

    def test_bad_env_value(self, monkeypatch):
        from repro import kernels

        monkeypatch.setenv(kernels.BACKEND_ENV, "gpu")
        with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
            kernels.kernel_backend()


class TestShapeValidation:
    """Non-tile-divisible shapes fail fast, naming the offending dim."""

    def test_matmul_bad_k(self):
        x = jnp.ones((128, 300))
        w = jnp.ones((300, 128))
        with pytest.raises(ValueError, match=r"K=300.*block size 128"):
            streaming_matmul(x, w, block_m=128, block_n=128, block_k=128,
                             interpret=True)

    def test_matmul_bad_m(self):
        x = jnp.ones((100, 256))
        w = jnp.ones((256, 128))
        with pytest.raises(ValueError, match=r"M=100"):
            streaming_matmul(x, w, block_m=64, block_n=128, block_k=128,
                             interpret=True)

    def test_matmul_k_mismatch(self):
        with pytest.raises(ValueError, match="contracting dims"):
            streaming_matmul(jnp.ones((128, 256)), jnp.ones((128, 256)),
                             interpret=True)

    def test_flash_bad_sq(self):
        q = jnp.ones((1, 4, 100, 32))
        k = jnp.ones((1, 2, 128, 32))
        with pytest.raises(ValueError, match=r"Sq=100"):
            flash_attention_tpu(q, k, k, block_q=64, block_k=64,
                                interpret=True)

    def test_flash_bad_gqa_group(self):
        q = jnp.ones((1, 3, 128, 32))
        k = jnp.ones((1, 2, 128, 32))
        with pytest.raises(ValueError, match="GQA group size"):
            flash_attention_tpu(q, k, k, block_q=64, block_k=64,
                                interpret=True)


class TestKernelGrads:
    """custom_vjp vs jax.grad through the jnp oracles."""

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-3), (jnp.bfloat16, 0.6)])
    def test_matmul_grads(self, dtype, tol):
        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        x = jax.random.normal(ks[0], (128, 256), jnp.float32).astype(dtype)
        w = jax.random.normal(ks[1], (256, 128), jnp.float32).astype(dtype)

        def loss_kernel(x, w):
            y = streaming_matmul(x, w, block_m=128, block_n=128,
                                 block_k=128, interpret=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        def loss_ref(x, w):
            return jnp.sum(ref.matmul_ref(x, w).astype(jnp.float32) ** 2)

        gx, gw = jax.grad(loss_kernel, argnums=(0, 1))(x, w)
        rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        assert gx.dtype == x.dtype and gw.dtype == w.dtype
        np.testing.assert_allclose(gx.astype(jnp.float32) / 256,
                                   rx.astype(jnp.float32) / 256,
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(gw.astype(jnp.float32) / 256,
                                   rw.astype(jnp.float32) / 256,
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("B,H,KV,S,D,causal,window", [
        (1, 4, 2, 128, 32, True, None),     # GQA causal
        (1, 4, 4, 128, 32, False, None),    # MHA full
        (2, 4, 1, 128, 32, True, 64),       # MQA + sliding window
    ])
    def test_flash_grads(self, B, H, KV, S, D, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (B, H, S, D))
        k = jax.random.normal(ks[1], (B, KV, S, D))
        v = jax.random.normal(ks[2], (B, KV, S, D))

        def loss_kernel(q, k, v):
            o = flash_attention_tpu(q, k, v, causal=causal, window=window,
                                    block_q=64, block_k=64, interpret=True)
            return jnp.sum(o ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(ref.flash_ref(q, k, v, causal=causal,
                                         window=window) ** 2)

        got = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(g, r, atol=2e-3, rtol=2e-3,
                                       err_msg=f"d{name} mismatch")

    def test_flash_grads_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (1, 4, 128, 32)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 128, 32)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 128, 32)).astype(jnp.bfloat16)

        def loss(q, k, v):
            o = flash_attention_tpu(q, k, v, block_q=64, block_k=64,
                                    interpret=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(ref.flash_ref(q, k, v).astype(jnp.float32) ** 2)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r, name in zip(got, want, "qkv"):
            assert g.dtype == jnp.bfloat16
            np.testing.assert_allclose(g.astype(jnp.float32),
                                       r.astype(jnp.float32),
                                       atol=0.15, rtol=0.15,
                                       err_msg=f"d{name} mismatch")

    def test_grad_through_ops_matmul(self):
        """The ops-layer wrapper is differentiable too (exec path uses it)."""
        x = jax.random.normal(jax.random.PRNGKey(10), (128, 256))
        w = jax.random.normal(jax.random.PRNGKey(11), (256, 128))
        g = jax.grad(lambda w: jnp.sum(
            ops.matmul(x, w, block_m=128, block_n=128, block_k=128,
                       interpret=True)))(w)
        r = jax.grad(lambda w: jnp.sum(ref.matmul_ref(x, w)))(w)
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)
