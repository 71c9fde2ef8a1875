"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed with interpret=True (the CPU-container contract for TPU kernels)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode_gqa
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


class TestFlashDecode:
    @pytest.mark.parametrize("B,Hq,Hkv,D,S", [
        (2, 8, 2, 128, 512),
        (1, 16, 8, 128, 1024),
        (4, 4, 1, 64, 256),
        (2, 12, 4, 128, 384),    # non-pow2 S with block 128
        (1, 71, 71, 64, 256),    # falcon-7b-like MHA head count
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, Hq, Hkv, D, S, dtype):
        q = jnp.asarray(RNG.normal(size=(B, Hq, D)), dtype)
        k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), dtype)
        v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), dtype)
        pos = S - 1
        out = flash_decode_gqa(q, k, v, pos, block_s=128, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype))

    @pytest.mark.parametrize("pos", [0, 5, 255, 400])
    def test_masking_positions(self, pos):
        B, Hq, Hkv, D, S = 2, 4, 2, 64, 512
        q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        out = flash_decode_gqa(q, k, v, pos, block_s=128, interpret=True)
        expect = ref.decode_attention_ref(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-4)

    def test_masked_tail_is_ignored(self):
        """Garbage beyond pos must not influence the output."""
        B, Hq, Hkv, D, S, pos = 1, 4, 2, 64, 256, 100
        q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        k2 = k.at[:, pos + 1:].set(1e4)
        v2 = v.at[:, pos + 1:].set(-1e4)
        a = flash_decode_gqa(q, k, v, pos, block_s=64, interpret=True)
        b = flash_decode_gqa(q, k2, v2, pos, block_s=64, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_agrees_with_model_decode_attention(self):
        """Kernel vs the model-side portable decode path."""
        from repro.models.attention import decode_attention
        B, Hq, Hkv, D, S, pos = 2, 8, 4, 64, 256, 255
        q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        a = flash_decode_gqa(q, k, v, pos, block_s=64, interpret=True)
        b = decode_attention(q, k, v, jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestSSDScan:
    @pytest.mark.parametrize("b,s,h,p,n,chunk", [
        (2, 256, 4, 64, 32, 64),
        (1, 128, 2, 32, 16, 32),
        (2, 64, 3, 16, 128, 64),
        (1, 512, 1, 64, 128, 128),   # mamba2-130m-like head
    ])
    def test_matches_sequential_oracle(self, b, s, h, p, n, chunk):
        xdt = jnp.asarray(RNG.normal(size=(b, s, h, p)) * 0.5, jnp.float32)
        dA = -jnp.abs(jnp.asarray(RNG.normal(size=(b, s, h)) * 0.3, jnp.float32))
        B = jnp.asarray(RNG.normal(size=(b, s, h, n)) * 0.5, jnp.float32)
        C = jnp.asarray(RNG.normal(size=(b, s, h, n)) * 0.5, jnp.float32)
        y, fin = ssd_scan(xdt, dA, B, C, chunk=chunk, interpret=True)
        y_ref, fin_ref = ref.ssd_scan_ref(xdt, dA, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_ref),
                                   atol=2e-4, rtol=1e-3)

    def test_models_ssm_chunked_matches_oracle(self):
        """The jnp SSD used by the model is equivalent to the kernel oracle."""
        from repro.models.ssm import ssd_chunked
        b, s, h, p, n = 2, 128, 4, 32, 16
        xdt = jnp.asarray(RNG.normal(size=(b, s, h, p)) * 0.5, jnp.float32)
        dA = -jnp.abs(jnp.asarray(RNG.normal(size=(b, s, h)) * 0.3, jnp.float32))
        B = jnp.asarray(RNG.normal(size=(b, s, h, n)) * 0.5, jnp.float32)
        C = jnp.asarray(RNG.normal(size=(b, s, h, n)) * 0.5, jnp.float32)
        y_m, fin_m = ssd_chunked(xdt, dA, B, C, 32)
        y_r, fin_r = ref.ssd_scan_ref(xdt, dA, B, C)
        np.testing.assert_allclose(np.asarray(y_m, np.float32),
                                   np.asarray(y_r), atol=2e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(fin_m), np.asarray(fin_r),
                                   atol=2e-4, rtol=1e-3)


class TestRGLRU:
    @pytest.mark.parametrize("B,S,W,bs,bw", [
        (2, 256, 128, 64, 64),
        (1, 128, 512, 128, 256),
        (3, 64, 64, 32, 64),
        (1, 1024, 256, 256, 128),
    ])
    def test_matches_oracle(self, B, S, W, bs, bw):
        a = jnp.asarray(RNG.uniform(0.7, 0.999, (B, S, W)), jnp.float32)
        b = jnp.asarray(RNG.normal(size=(B, S, W)) * 0.1, jnp.float32)
        out = rglru_scan_pallas(a, b, block_s=bs, block_w=bw, interpret=True)
        expect = ref.rglru_scan_ref(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-4, rtol=1e-4)

    def test_model_rglru_matches_kernel_ref(self):
        """models.hybrid's associative_scan == the kernel oracle."""
        from repro.models.hybrid import rglru_scan as model_scan
        W = 64
        pl = {
            "w_a": jnp.asarray(RNG.normal(size=(W, W)) * 0.05, jnp.float32),
            "b_a": jnp.zeros((W,), jnp.float32),
            "w_i": jnp.asarray(RNG.normal(size=(W, W)) * 0.05, jnp.float32),
            "b_i": jnp.zeros((W,), jnp.float32),
            "lam": jnp.ones((W,), jnp.float32),
        }
        u = jnp.asarray(RNG.normal(size=(2, 32, W)), jnp.float32)
        h, h_last = model_scan(pl, u)
        from repro.models.hybrid import _lru_coeffs
        a, b = _lru_coeffs(pl, u)
        expect = ref.rglru_scan_ref(a, b)
        np.testing.assert_allclose(np.asarray(h), np.asarray(expect),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(expect[:, -1]),
                                   atol=1e-5)


class TestOpsWrappers:
    def test_jitted_wrappers(self):
        B, Hq, Hkv, D, S = 1, 4, 2, 64, 128
        q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
        out = ops.decode_attention(q, k, v, jnp.asarray(S - 1), block_s=64,
                                   interpret=True)
        assert out.shape == (B, Hq, D)
        a = jnp.asarray(RNG.uniform(0.8, 0.99, (1, 64, 64)), jnp.float32)
        b = jnp.asarray(RNG.normal(size=(1, 64, 64)), jnp.float32)
        h = ops.rglru(a, b, block_s=32, block_w=64, interpret=True)
        assert h.shape == a.shape


class TestCausalFlashPrefill:
    """The flash kernel that `models.attention.full_attention` takes for
    causal self-attention lowered for TPU, and the dispatch to it."""

    B, HQ, HKV, D = 2, 16, 8, 128

    def _qkv(self, S, dtype=jnp.bfloat16):
        ks = jax.random.split(jax.random.PRNGKey(S), 3)
        q = jax.random.normal(ks[0], (self.B, S, self.HQ, self.D), dtype)
        k = jax.random.normal(ks[1], (self.B, S, self.HKV, self.D), dtype)
        v = jax.random.normal(ks[2], (self.B, S, self.HKV, self.D), dtype)
        return q, k, v

    @pytest.mark.parametrize("S, block", [(256, 256), (768, 256),
                                          (1280, 256), (1536, 512)])
    def test_matches_jnp_attention(self, S, block):
        from repro.models import attention as A

        assert A.flash_block(S) == block
        q, k, v = self._qkv(S)
        want = A.chunked_attention(q, k, v, causal=True, window=0,
                                   q_offset=0, chunk_q=512, softcap=0.0)
        got = A.causal_flash_attention(q, k, v, interpret=True)
        want = np.asarray(want, np.float32)
        # Both paths keep f32 scores and sums but hand bf16 on at three
        # points, each rounded to half a bf16 ulp: q after the folded
        # 1/sqrt(D) (the jnp path scales f32 scores), the probabilities
        # entering the PV product, and the output. Two ulps of the largest
        # output bound their difference; the paths differ by one ulp here.
        tol = 2 * float(jnp.finfo(jnp.bfloat16).eps) * np.abs(want).max()
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=0, atol=tol)

    def test_gradients_match_jnp_attention(self):
        """A training step lowered for TPU differentiates through the
        kernel's own backward kernels; in f32 they give the jnp path's
        gradients to f32 rounding."""
        from repro.models import attention as A

        B, S, HQ, HKV, D = 1, 256, 4, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, S, HQ, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, HKV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, HKV, D), jnp.float32)

        def loss(attend):
            return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

        flash = partial(A.causal_flash_attention, interpret=True)
        grad = partial(jax.grad, argnums=(0, 1, 2))
        lowered = jax.jit(grad(loss(A.full_attention))).trace(q, k, v).lower(
            lowering_platforms=("tpu",))
        assert lowered.as_text().count("tpu_custom_call") == 3  # fwd, dq, dkv
        want = grad(loss(A.full_attention))(q, k, v)
        got = grad(loss(flash))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=0,
                                       atol=1e-5 * np.abs(w).max())

    @pytest.mark.parametrize("S, kw, kernels", [
        (256, {}, 1),
        (256, {"window": 64}, 0),
        (256, {"softcap": 30.0}, 0),
        (256, {"causal": False}, 0),
        (256, {"q_offset": 128}, 0),
        (256, {"kv_len": 384}, 0),
        (320, {}, 0),
    ])
    def test_dispatch(self, S, kw, kernels):
        """Lowered for TPU, only causal self-attention with no window, soft
        cap or offset whose length divides into 128 holds a Mosaic call;
        every call gives what the jnp path gives, bit for bit, on the CPU."""
        from repro.models import attention as A

        kw = dict(kw)
        q, _, _ = self._qkv(S)
        _, k, v = self._qkv(kw.pop("kv_len", S))
        args = dict(causal=True, window=0, q_offset=0, chunk_q=512,
                    softcap=0.0) | kw
        f = jax.jit(lambda q, k, v: A.full_attention(q, k, v, **kw))
        hlo = f.trace(q, k, v).lower(lowering_platforms=("tpu",)).as_text()
        assert hlo.count("tpu_custom_call") == kernels
        jnp_path = jax.jit(lambda q, k, v: A.chunked_attention(q, k, v, **args))
        np.testing.assert_array_equal(np.asarray(f(q, k, v), np.float32),
                                      np.asarray(jnp_path(q, k, v), np.float32))
