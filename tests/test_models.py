"""Model tests: encoder/decoder forward, decode loop, weights IO, training."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import genserve_harness
from genserve_harness import stop_what_the_test_started  # noqa: F401
from nornicdb_tpu.models import bge_m3, layers, qwen2, training, weights
from nornicdb_tpu.models.tokenizer import HashTokenizer
from nornicdb_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def bge_params():
    return bge_m3.init_params(bge_m3.BGE_SMALL, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def qwen_params():
    return qwen2.init_params(qwen2.QWEN_SMALL, jax.random.PRNGKey(0))


class TestBge:
    def test_forward_shape_and_norm(self, bge_params):
        cfg = bge_m3.BGE_SMALL
        ids = jnp.asarray([[0, 5, 6, 2], [0, 7, 2, 1]], jnp.int32)
        mask = jnp.asarray([[1, 1, 1, 1], [1, 1, 1, 0]], jnp.int32)
        emb = bge_m3.forward(bge_params, cfg, ids, mask)
        assert emb.shape == (2, cfg.dims)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(emb), axis=1), 1.0, atol=1e-5
        )

    def test_padding_invariance(self, bge_params):
        """Extra padding must not change the embedding (mask correctness)."""
        cfg = bge_m3.BGE_SMALL
        ids1 = jnp.asarray([[0, 5, 6, 2]], jnp.int32)
        mask1 = jnp.asarray([[1, 1, 1, 1]], jnp.int32)
        ids2 = jnp.asarray([[0, 5, 6, 2, 1, 1, 1, 1]], jnp.int32)
        mask2 = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32)
        e1 = np.asarray(bge_m3.forward(bge_params, cfg, ids1, mask1))
        e2 = np.asarray(bge_m3.forward(bge_params, cfg, ids2, mask2))
        np.testing.assert_allclose(e1, e2, atol=2e-2)

    def test_deterministic(self, bge_params):
        cfg = bge_m3.BGE_SMALL
        ids = jnp.asarray([[0, 9, 2]], jnp.int32)
        mask = jnp.ones_like(ids)
        e1 = np.asarray(bge_m3.forward(bge_params, cfg, ids, mask))
        e2 = np.asarray(bge_m3.forward(bge_params, cfg, ids, mask))
        np.testing.assert_array_equal(e1, e2)

    def test_real_config_shapes(self):
        # param-count sanity for the full bge-m3 (~568M); init only 2 layers
        cfg = bge_m3.BGE_M3
        assert cfg.hidden == 1024 and cfg.layers == 24 and cfg.vocab_size == 250002


class TestQwen:
    def test_forward_logits(self, qwen_params):
        cfg = qwen2.QWEN_SMALL
        ids = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        logits = qwen2.forward(qwen_params, cfg, ids)
        assert logits.shape == (1, 4, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_causality(self, qwen_params):
        """Changing a future token must not change past logits."""
        cfg = qwen2.QWEN_SMALL
        a = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        b = jnp.asarray([[1, 2, 3, 9]], jnp.int32)
        la = np.asarray(qwen2.forward(qwen_params, cfg, a))
        lb = np.asarray(qwen2.forward(qwen_params, cfg, b))
        np.testing.assert_allclose(la[:, :3], lb[:, :3], atol=1e-4)
        assert np.abs(la[:, 3] - lb[:, 3]).max() > 1e-3

    def test_served_decode_matches_full_forward(self, qwen_params):
        """The engine's greedy continuation == argmax over repeated full
        forwards: at every position the served token's ``forward`` logit is
        that forward's best, within the rounding of two programs that
        reduce in different orders (``genserve_harness.GAP_TOL``)."""
        cfg = qwen2.QWEN_SMALL
        prompt = [1, 2, 3]
        eng = genserve_harness.engine()
        got = eng.generate(prompt, max_new_tokens=5)
        assert len(got) == 5
        for n, tok in enumerate(got):
            logits = np.asarray(qwen2.forward(
                qwen_params, cfg, jnp.asarray([prompt + got[:n]], jnp.int32)
            ))[0, -1]
            assert logits.max() - logits[tok] < genserve_harness.GAP_TOL

    def test_runs_to_max_new_tokens_where_eos_never_comes(self):
        class NeverEnds:
            eos_id = 99999  # outside the vocabulary: never sampled

        eng = genserve_harness.engine(tokenizer=NeverEnds())
        out = eng.generate([1, 2], max_new_tokens=8)
        assert len(out) == 8  # eos never sampled -> full length


class TestGroupedAttention:
    """``layers.grouped_attention`` (every Qwen path's attention: K/V rows
    as the page pool stores them, no ``repeat_kv`` copy) against the
    repeated form it replaces.  The two differ by order of reduction only."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("all_masked", [False, True],
                             ids=["causal", "row-all-masked"])
    @pytest.mark.parametrize("heads,kv_heads", [(14, 2), (4, 4)])
    @pytest.mark.parametrize("t", [1, 64])
    def test_matches_repeated_attention(self, t, heads, kv_heads,
                                        all_masked, dtype):
        b, s_len, dh = 3, 128, 64
        rng = np.random.default_rng(t * 100 + heads)
        q = jnp.asarray(rng.standard_normal((b, t, heads, dh)), dtype)
        k = jnp.asarray(rng.standard_normal((b, s_len, kv_heads, dh)), dtype)
        v = jnp.asarray(rng.standard_normal((b, s_len, kv_heads, dh)), dtype)
        # sequence i has 40 + 30 i slots behind its first query
        pos = 40 + 30 * np.arange(b)[:, None] + np.arange(t)[None]
        open_ = np.arange(s_len)[None, None] <= pos[:, :, None]
        if all_masked:
            open_[1] = False  # a padding lane: finite, uniform over garbage
        mask = jnp.asarray(np.where(open_, 0.0, -1e30)[:, None], jnp.float32)
        got = layers.grouped_attention(
            q, k.reshape(b, s_len, -1), v.reshape(b, s_len, -1), mask)
        n_rep = heads // kv_heads
        want = layers.attention(
            q, layers.repeat_kv(k, n_rep), layers.repeat_kv(v, n_rep), mask)
        assert got.shape == want.shape and got.dtype == want.dtype
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        if dtype == "float32":
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
        else:  # one unit in bf16's last place (8 significant bits)
            big = np.maximum(np.abs(got), np.abs(want))
            ulp = 2.0 ** (np.floor(np.log2(np.maximum(big, 1e-30))) - 7)
            assert (np.abs(got - want) <= ulp).all()


class TestTokenizer:
    def test_stable_and_bounded(self):
        tok = HashTokenizer(256)
        a = tok.encode("hello world")
        b = tok.encode("hello world")
        assert a == b
        assert all(0 <= t < 256 for t in a)
        assert a[0] == tok.cls_id and a[-1] == tok.eos_id

    def test_batch_padding(self):
        tok = HashTokenizer(256)
        ids, masks = tok.encode_batch(["one two three", "one"])
        assert len(ids[0]) == len(ids[1])
        assert masks[1][-1] == 0


class TestWeights:
    def test_safetensors_roundtrip(self, tmp_path):
        tensors = {
            "a.w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.asarray([1, 2, 3], np.int64),
        }
        p = str(tmp_path / "m.safetensors")
        weights.save_safetensors(p, tensors)
        back = weights.load_safetensors(p)
        np.testing.assert_array_equal(back["a.w"], tensors["a.w"])
        np.testing.assert_array_equal(back["b"], tensors["b"])

    def test_params_roundtrip(self, tmp_path, qwen_params):
        p = str(tmp_path / "qwen.safetensors")
        weights.save_params(p, qwen_params)
        loaded = weights.load_params(p, qwen_params)
        for a, b in zip(jax.tree.leaves(qwen_params), jax.tree.leaves(loaded)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-2
            )


class TestTraining:
    def test_loss_decreases_single_device(self):
        cfg = bge_m3.BGE_SMALL
        opt = training.make_optimizer(1e-3)
        state = training.init_train_state(cfg, opt, seed=1)
        step = training.make_train_step(cfg, opt)
        rng = np.random.default_rng(0)
        batch = {
            "ids_a": jnp.asarray(rng.integers(4, 1000, (8, 16)), jnp.int32),
            "mask_a": jnp.ones((8, 16), jnp.int32),
            "ids_b": jnp.asarray(rng.integers(4, 1000, (8, 16)), jnp.int32),
            "mask_b": jnp.ones((8, 16), jnp.int32),
        }
        # positive pairs = same text
        batch["ids_b"] = batch["ids_a"]
        losses = []
        for _ in range(5):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_sharded_train_step_runs(self):
        mesh = make_mesh({"data": 4, "model": 2})
        cfg = bge_m3.BGE_SMALL
        opt = training.make_optimizer(1e-3)
        state = training.init_train_state(cfg, opt, seed=2)
        state = training.shard_train_state(state, cfg, mesh)
        step = training.make_sharded_train_step(cfg, opt, mesh)
        rng = np.random.default_rng(1)
        batch = {
            "ids_a": jnp.asarray(rng.integers(4, 1000, (8, 16)), jnp.int32),
            "mask_a": jnp.ones((8, 16), jnp.int32),
            "ids_b": jnp.asarray(rng.integers(4, 1000, (8, 16)), jnp.int32),
            "mask_b": jnp.ones((8, 16), jnp.int32),
        }
        batch = training.shard_batch(batch, mesh)
        state2, loss = step(state, batch)
        assert np.isfinite(float(loss))
        # params keep their TP sharding after the update
        qshard = state2.params["blocks"][0]["q"]["w"].sharding
        assert "model" in str(qshard.spec) or qshard.is_fully_replicated is False

    def test_sharded_matches_unsharded(self):
        cfg = bge_m3.BGE_SMALL
        opt = training.make_optimizer(1e-3)
        mesh = make_mesh({"data": 4, "model": 2})
        rng = np.random.default_rng(2)
        batch = {
            "ids_a": jnp.asarray(rng.integers(4, 1000, (8, 16)), jnp.int32),
            "mask_a": jnp.ones((8, 16), jnp.int32),
            "ids_b": jnp.asarray(rng.integers(4, 1000, (8, 16)), jnp.int32),
            "mask_b": jnp.ones((8, 16), jnp.int32),
        }
        s1 = training.init_train_state(cfg, opt, seed=3)
        _, loss1 = training.make_train_step(cfg, opt)(s1, batch)
        s2 = training.init_train_state(cfg, opt, seed=3)
        s2 = training.shard_train_state(s2, cfg, mesh)
        _, loss2 = training.make_sharded_train_step(cfg, opt, mesh)(
            s2, training.shard_batch(batch, mesh)
        )
        assert float(loss1) == pytest.approx(float(loss2), abs=2e-2)


class TestGGUF:
    """(ref: lib/llama/gguf.h, neural/export_to_gguf.py)"""

    def test_metadata_and_tensor_roundtrip(self, tmp_path):
        from nornicdb_tpu.models import gguf

        meta = {
            "general.architecture": "bert",
            "general.name": "test-model",
            "bert.embedding_length": 128,
            "bert.block_count": 2,
            "general.alignment": 32,
            "tokenizer.ggml.tokens": ["<s>", "</s>", "hello"],
            "some.float": 1.5,
            "some.bool": True,
        }
        rng = np.random.default_rng(0)
        tensors = {
            "token_embd.weight": rng.standard_normal((64, 128)).astype(np.float32),
            "blk.0.attn_q.weight": rng.standard_normal((128, 128)).astype(np.float16),
            "output_norm.bias": rng.standard_normal(128).astype(np.float32),
        }
        p = str(tmp_path / "m.gguf")
        gguf.save_gguf(p, meta, tensors)
        meta2, tensors2 = gguf.load_gguf(p)
        assert meta2["general.architecture"] == "bert"
        assert meta2["bert.embedding_length"] == 128
        assert meta2["tokenizer.ggml.tokens"] == ["<s>", "</s>", "hello"]
        assert meta2["some.bool"] is True
        for name, arr in tensors.items():
            np.testing.assert_array_equal(tensors2[name], arr)

    def test_params_from_gguf(self, tmp_path, qwen_params):
        from nornicdb_tpu.models import gguf, weights

        flat = weights.flatten_params(qwen_params)
        tensors = {f"t.{k}": np.asarray(v, np.float32) for k, v in flat.items()}
        p = str(tmp_path / "qwen.gguf")
        gguf.save_gguf(p, {"general.architecture": "qwen2"}, tensors)
        loaded = gguf.load_params_from_gguf(
            p, qwen_params, lambda k: f"t.{k}"
        )
        for a, b in zip(jax.tree.leaves(qwen_params), jax.tree.leaves(loaded)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
            )

    def test_rejects_quantized(self, tmp_path):
        from nornicdb_tpu.models import gguf
        import struct as _s

        p = str(tmp_path / "q.gguf")
        gguf.save_gguf(p, {}, {"w": np.zeros((4, 4), np.float32)})
        raw = bytearray(open(p, "rb").read())
        # patch the tensor dtype field to a quant type without a decoder
        # (Q2_K = 10; the standard formats now dequantize, round 2)
        base = 4 + 4 + 16  # magic+version+counts
        name_block = 8 + 1 + 4 + 16
        dtype_off = base + name_block
        _s.pack_into("<I", raw, dtype_off, 10)
        open(p, "wb").write(bytes(raw))
        with pytest.raises(ValueError, match="not supported"):
            gguf.load_gguf(p)


class TestGGUFQuantized:
    """Quantized GGUF block decode, verified with synthetic tensors against
    scalar straight-from-spec references (ref: lib/llama/gguf.h block
    layouts; pkg/localllm/llama.go:498 consumes Q-quantized files)."""

    def _scalar_dequant(self, ggml_type, raw, count):
        """Loop-based reference decoder, written directly from the public
        GGML block layout (independent of the vectorized implementation)."""
        import struct as st

        import numpy as np

        from nornicdb_tpu.models import gguf as G

        elems, nbytes = G._QUANT_BLOCKS[ggml_type]
        out = []
        for b in range(count // elems):
            blk = raw[b * nbytes:(b + 1) * nbytes]
            if ggml_type == G.GGML_Q8_0:
                d = np.frombuffer(blk[:2], np.float16)[0]
                qs = np.frombuffer(blk[2:], np.int8)
                out.extend(float(d) * q for q in qs)
            elif ggml_type == G.GGML_Q4_0:
                d = float(np.frombuffer(blk[:2], np.float16)[0])
                qs = blk[2:]
                vals = [0.0] * 32
                for i in range(16):
                    vals[i] = d * ((qs[i] & 0xF) - 8)
                    vals[i + 16] = d * ((qs[i] >> 4) - 8)
                out.extend(vals)
            elif ggml_type == G.GGML_Q4_1:
                d = float(np.frombuffer(blk[0:2], np.float16)[0])
                m = float(np.frombuffer(blk[2:4], np.float16)[0])
                qs = blk[4:]
                vals = [0.0] * 32
                for i in range(16):
                    vals[i] = d * (qs[i] & 0xF) + m
                    vals[i + 16] = d * (qs[i] >> 4) + m
                out.extend(vals)
            elif ggml_type == G.GGML_Q5_0:
                d = float(np.frombuffer(blk[0:2], np.float16)[0])
                (qh,) = st.unpack("<I", blk[2:6])
                qs = blk[6:]
                vals = [0.0] * 32
                for i in range(16):
                    lo = (qs[i] & 0xF) | (((qh >> i) & 1) << 4)
                    hi = (qs[i] >> 4) | (((qh >> (i + 16)) & 1) << 4)
                    vals[i] = d * (lo - 16)
                    vals[i + 16] = d * (hi - 16)
                out.extend(vals)
            elif ggml_type == G.GGML_Q5_1:
                d = float(np.frombuffer(blk[0:2], np.float16)[0])
                m = float(np.frombuffer(blk[2:4], np.float16)[0])
                (qh,) = st.unpack("<I", blk[4:8])
                qs = blk[8:]
                vals = [0.0] * 32
                for i in range(16):
                    lo = (qs[i] & 0xF) | (((qh >> i) & 1) << 4)
                    hi = (qs[i] >> 4) | (((qh >> (i + 16)) & 1) << 4)
                    vals[i] = d * lo + m
                    vals[i + 16] = d * hi + m
                out.extend(vals)
            elif ggml_type == G.GGML_Q4_K:
                d = float(np.frombuffer(blk[0:2], np.float16)[0])
                dmin = float(np.frombuffer(blk[2:4], np.float16)[0])
                sc = blk[4:16]
                qs = blk[16:144]
                vals = [0.0] * 256

                def scale_min(j):
                    if j < 4:
                        return sc[j] & 63, sc[j + 4] & 63
                    return ((sc[j + 4] & 0xF) | ((sc[j - 4] >> 6) << 4),
                            (sc[j + 4] >> 4) | ((sc[j] >> 6) << 4))

                is_ = 0
                for j in range(0, 256, 64):
                    s1, m1 = scale_min(is_)
                    s2, m2 = scale_min(is_ + 1)
                    q = qs[(j // 2):(j // 2) + 32]
                    for l in range(32):
                        vals[j + l] = d * s1 * (q[l] & 0xF) - dmin * m1
                        vals[j + 32 + l] = d * s2 * (q[l] >> 4) - dmin * m2
                    is_ += 2
                out.extend(vals)
            elif ggml_type == G.GGML_Q6_K:
                ql = blk[0:128]
                qh = blk[128:192]
                sc = np.frombuffer(blk[192:208], np.int8)
                d = float(np.frombuffer(blk[208:210], np.float16)[0])
                vals = [0.0] * 256
                for half in range(2):
                    lq = ql[half * 64:half * 64 + 64]
                    hq = qh[half * 32:half * 32 + 32]
                    s = sc[half * 8:half * 8 + 8]
                    base = half * 128
                    for l in range(32):
                        isx = l // 16
                        q1 = ((lq[l] & 0xF) | (((hq[l] >> 0) & 3) << 4)) - 32
                        q2 = ((lq[l + 32] & 0xF)
                              | (((hq[l] >> 2) & 3) << 4)) - 32
                        q3 = ((lq[l] >> 4) | (((hq[l] >> 4) & 3) << 4)) - 32
                        q4 = ((lq[l + 32] >> 4)
                              | (((hq[l] >> 6) & 3) << 4)) - 32
                        vals[base + l] = d * s[isx + 0] * q1
                        vals[base + l + 32] = d * s[isx + 2] * q2
                        vals[base + l + 64] = d * s[isx + 4] * q3
                        vals[base + l + 96] = d * s[isx + 6] * q4
                out.extend(vals)
        import numpy as np

        return np.asarray(out, np.float32)

    def test_vectorized_matches_scalar_on_random_blocks(self):
        import numpy as np

        from nornicdb_tpu.models import gguf as G

        rng = np.random.default_rng(0)
        for t in (G.GGML_Q4_0, G.GGML_Q4_1, G.GGML_Q5_0, G.GGML_Q5_1,
                  G.GGML_Q8_0, G.GGML_Q4_K, G.GGML_Q6_K):
            elems, nbytes = G._QUANT_BLOCKS[t]
            blocks = 5
            raw = bytearray(rng.integers(0, 256, blocks * nbytes,
                                         dtype=np.uint8).tobytes())
            # keep the f16 scale fields finite (random bits can be NaN/inf)
            scale_offs = {G.GGML_Q4_0: [0], G.GGML_Q4_1: [0, 2],
                          G.GGML_Q5_0: [0], G.GGML_Q5_1: [0, 2],
                          G.GGML_Q8_0: [0], G.GGML_Q4_K: [0, 2],
                          G.GGML_Q6_K: [208]}[t]
            for b in range(blocks):
                for off in scale_offs:
                    v = np.float16(rng.uniform(-2, 2))
                    raw[b * nbytes + off:b * nbytes + off + 2] = v.tobytes()
            got = G.dequantize(bytes(raw), t, blocks * elems)
            want = self._scalar_dequant(t, bytes(raw), blocks * elems)
            assert np.allclose(got, want, rtol=1e-6, atol=1e-6), t

    def test_q8_0_roundtrip_accuracy(self):
        import numpy as np

        from nornicdb_tpu.models import gguf as G

        rng = np.random.default_rng(1)
        x = rng.standard_normal(32 * 64).astype(np.float32)
        back = G.dequantize(G.quantize_q8_0(x), G.GGML_Q8_0, x.size)
        # q8_0: ~8-bit relative precision per block
        scale = np.abs(x).reshape(-1, 32).max(axis=1).repeat(32)
        assert np.max(np.abs(back - x) / np.maximum(scale, 1e-9)) < 1.0 / 127

    def test_q4_0_roundtrip_accuracy(self):
        import numpy as np

        from nornicdb_tpu.models import gguf as G

        rng = np.random.default_rng(2)
        x = rng.standard_normal(32 * 64).astype(np.float32)
        back = G.dequantize(G.quantize_q4_0(x), G.GGML_Q4_0, x.size)
        scale = np.abs(x).reshape(-1, 32).max(axis=1).repeat(32)
        assert np.max(np.abs(back - x) / np.maximum(scale, 1e-9)) < 1.0 / 7

    def test_quantized_file_roundtrip(self, tmp_path):
        import numpy as np

        from nornicdb_tpu.models import gguf as G

        rng = np.random.default_rng(3)
        w = rng.standard_normal((16, 64)).astype(np.float32)
        p = str(tmp_path / "q.gguf")
        G.save_gguf(p, {"general.name": "quant-test"},
                    {"w_q8": w, "w_q4": w, "w_f32": w},
                    quantize={"w_q8": "q8_0", "w_q4": "q4_0"})
        meta, tensors = G.load_gguf(p)
        assert meta["general.name"] == "quant-test"
        assert tensors["w_f32"].shape == (16, 64)
        assert np.allclose(tensors["w_f32"], w)
        assert tensors["w_q8"].shape == (16, 64)
        err8 = np.max(np.abs(tensors["w_q8"] - w))
        err4 = np.max(np.abs(tensors["w_q4"] - w))
        assert err8 < 0.05 and err4 < 0.6
        assert err8 < err4  # more bits, less error

    def test_synthetic_k_quant_file(self, tmp_path):
        """A hand-built q6_K tensor round-trips through a real file."""
        import numpy as np

        from nornicdb_tpu.models import gguf as G

        rng = np.random.default_rng(4)
        elems, nbytes = G._QUANT_BLOCKS[G.GGML_Q6_K]
        raw = bytearray(rng.integers(0, 256, 2 * nbytes,
                                     dtype=np.uint8).tobytes())
        for b in range(2):
            v = np.float16(0.25)
            raw[b * nbytes + 208:b * nbytes + 210] = v.tobytes()
        p = str(tmp_path / "k.gguf")
        G.save_gguf(p, {}, {},
                    raw_tensors={"w": (G.GGML_Q6_K, (2, 256), bytes(raw))})
        _, tensors = G.load_gguf(p)
        want = self._scalar_dequant(G.GGML_Q6_K, bytes(raw), 512)
        assert np.allclose(tensors["w"].reshape(-1), want)

    def test_bf16_tensor(self, tmp_path):
        import numpy as np

        from nornicdb_tpu.models import gguf as G

        x = np.asarray([1.5, -2.25, 0.0, 3.0], np.float32)
        u16 = (x.view(np.uint32) >> 16).astype(np.uint16)
        p = str(tmp_path / "bf.gguf")
        G.save_gguf(p, {}, {},
                    raw_tensors={"w": (G.GGML_BF16, (4,), u16.tobytes())})
        _, tensors = G.load_gguf(p)
        assert np.allclose(tensors["w"], x)  # exact: values are bf16-clean
