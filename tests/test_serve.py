"""LM serving: decode-vs-forward consistency and the continuous-batching
server (slot recycling, per-slot positions, ring-buffer windows)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.common import DEFAULT_POLICY
from repro.serve.engine import DecodeServer, Request


@pytest.fixture(scope="module")
def tiny():
    cfg = T.LMConfig(
        name="tiny", n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=128, q_chunk=16, loss_chunk=16,
    )
    params = T.init_lm(jax.random.PRNGKey(0), cfg, DEFAULT_POLICY)
    return cfg, params


def greedy_via_backbone(params, cfg, prompt, n_new):
    """Oracle: full forward at every step (no cache)."""
    toks = list(prompt)
    for _ in range(n_new):
        h, _ = T.lm_backbone(params, jnp.asarray([toks], jnp.int32), cfg)
        head = T._unembed(params, cfg).astype(jnp.bfloat16)
        logits = jnp.einsum("d,dv->v", h[0, -1], head)
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


def greedy_via_decode(params, cfg, prompt, n_new, max_len=64):
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), T.cache_spec(cfg, 1, max_len)
    )
    out = []
    tok = prompt[0]
    for pos in range(len(prompt) + n_new - 1):
        logits, cache = T.lm_decode_step(
            params, cache, jnp.asarray([[tok]], jnp.int32),
            jnp.int32(pos), cfg,
        )
        if pos + 1 < len(prompt):
            tok = prompt[pos + 1]
        else:
            tok = int(jnp.argmax(logits[0]))
            out.append(tok)
    return out


class TestDecodeConsistency:
    def test_cached_decode_equals_full_forward(self, tiny):
        cfg, params = tiny
        prompt = [3, 17, 42, 7]
        want = greedy_via_backbone(params, cfg, prompt, 6)
        got = greedy_via_decode(params, cfg, prompt, 6)
        assert got == want

    def test_windowed_decode_ring_buffer(self):
        """A ring cache of `window` slots decodes identically to a full
        cache when attention is windowed (starcoder2's long_500k path)."""
        cfg = T.LMConfig(
            name="sw", n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
            d_ff=64, vocab=64, window=8, q_chunk=8, loss_chunk=8,
        )
        params = T.init_lm(jax.random.PRNGKey(1), cfg, DEFAULT_POLICY)
        prompt = [5, 9, 2, 33, 8, 1, 60, 4, 22, 11]
        full = greedy_via_decode(params, cfg, prompt, 8, max_len=64)
        ring = greedy_via_decode(params, cfg, prompt, 8, max_len=8)  # =window
        assert ring == full


class TestServer:
    def test_continuous_batching(self, tiny):
        cfg, params = tiny
        srv = DecodeServer(params, cfg, batch_slots=3, max_len=48)
        reqs = [
            Request(rid=i, prompt=[int(x) for x in p], max_new=5)
            for i, p in enumerate(
                [[3, 17, 42], [7, 7], [1, 2, 3, 4], [9], [12, 13]]
            )
        ]
        done, steps = srv.run(reqs)
        assert len(done) == 5  # 5 requests through 3 slots
        for r in reqs:
            assert r.done and len(r.out) == 5
        # per-request outputs match the single-sequence oracle
        for r in reqs[:2]:
            want = greedy_via_backbone(params, cfg, r.prompt, 5)
            assert r.out == want, (r.rid, r.out, want)

    def test_slot_reuse(self, tiny):
        cfg, params = tiny
        srv = DecodeServer(params, cfg, batch_slots=1, max_len=32)
        reqs = [Request(rid=i, prompt=[i + 1], max_new=3) for i in range(3)]
        done, _ = srv.run(reqs)
        assert len(done) == 3  # sequential through one slot


class TestMLADecodeParity:
    def test_absorbed_decode_equals_expanded_forward(self):
        """The weight-absorbed MLA decode (latent-space scores over the
        compressed cache) must match the expanded-form backbone."""
        from repro.models.transformer import MLAConfig

        cfg = T.LMConfig(
            name="mla-tiny", n_layers=2, d_model=48, n_heads=4, n_kv_heads=4,
            d_ff=96, vocab=96,
            mla=MLAConfig(q_lora_rank=24, kv_lora_rank=12,
                          qk_nope_head_dim=12, qk_rope_head_dim=8,
                          v_head_dim=12),
            q_chunk=8, loss_chunk=8,
        )
        params = T.init_lm(jax.random.PRNGKey(3), cfg, DEFAULT_POLICY)
        prompt = [5, 61, 17, 40, 2]
        # absorbed ((q·Wk)·c) vs expanded (q·(Wk·c)) reassociates bf16
        # matmuls — compare logits within bf16 tolerance + argmax equality
        h, _ = T.lm_backbone(params, jnp.asarray([prompt], jnp.int32), cfg)
        head = T._unembed(params, cfg).astype(jnp.bfloat16)
        logits_fwd = jnp.einsum("sd,dv->sv", h[0], head).astype(jnp.float32)
        cache = jax.tree.map(
            lambda s_: jnp.zeros(s_.shape, s_.dtype), T.cache_spec(cfg, 1, 16)
        )
        dec = []
        for pos, tok in enumerate(prompt):
            lg, cache = T.lm_decode_step(
                params, cache, jnp.asarray([[tok]], jnp.int32),
                jnp.int32(pos), cfg,
            )
            dec.append(lg[0])
        logits_dec = jnp.stack(dec).astype(jnp.float32)
        np.testing.assert_allclose(
            np.asarray(logits_dec), np.asarray(logits_fwd), atol=0.08
        )
        assert bool(
            (jnp.argmax(logits_fwd, -1) == jnp.argmax(logits_dec, -1)).all()
        )


class TestMixedCache:
    def test_mixed_cache_decode_matches_stacked(self):
        """Per-layer mixed-window caches (local ring = window slots) must
        decode identically to the uniform full-length stacked cache."""
        cfg = T.LMConfig(
            name="lg", n_layers=6, d_model=32, n_heads=2, n_kv_heads=2,
            d_ff=64, vocab=64, window=4, global_every=3,
            q_chunk=8, loss_chunk=8,
        )
        params = T.init_lm(jax.random.PRNGKey(7), cfg, DEFAULT_POLICY)
        toks = [3, 9, 33, 60, 12, 5, 48, 20, 7, 41]
        max_len = 16
        # stacked full cache
        cache_s = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            T.cache_spec(cfg, 1, max_len),
        )
        # mixed per-layer cache (locals hold only `window` slots)
        cache_m = [
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
            for spec in T.cache_spec_mixed(cfg, 1, max_len)
        ]
        sizes = [c["k"].shape[1] for c in cache_m]
        assert sizes == [4, 4, 16, 4, 4, 16]  # 2:1 pattern of this config
        # full-length per-layer list: isolates ring-size from the
        # (bf16-reassociating) scan-vs-unrolled execution difference
        cache_f = [
            jax.tree.map(
                lambda s: jnp.zeros((1, max_len) + s.shape[2:], s.dtype), spec
            )
            for spec in T.cache_spec_mixed(cfg, 1, max_len)
        ]
        for pos, tok in enumerate(toks):
            t = jnp.asarray([[tok]], jnp.int32)
            lg_s, cache_s = T.lm_decode_step(
                params, cache_s, t, jnp.int32(pos), cfg
            )
            lg_m, cache_m = T.lm_decode_step(
                params, cache_m, t, jnp.int32(pos), cfg
            )
            lg_f, cache_f = T.lm_decode_step(
                params, cache_f, t, jnp.int32(pos), cfg
            )
            # ring caches are EXACTLY equivalent to full-length caches
            np.testing.assert_array_equal(
                np.asarray(lg_m), np.asarray(lg_f)
            )
            # and match the scanned stacked path within bf16 reassociation
            np.testing.assert_allclose(
                np.asarray(lg_m).astype(np.float32),
                np.asarray(lg_s).astype(np.float32),
                atol=5e-2,
            )
