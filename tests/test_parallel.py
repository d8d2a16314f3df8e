"""Parallel layer tests on the 8-device virtual CPU mesh (conftest forces
--xla_force_host_platform_device_count=8, mirroring how the reference tests
replication without a cluster)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nornicdb_tpu.ops import DeviceCorpus
from nornicdb_tpu.parallel import (
    ShardedCorpus,
    make_mesh,
    make_ring_attention,
    reference_attention,
)


def _rand(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


class TestMesh:
    def test_default_mesh_all_devices(self):
        mesh = make_mesh()
        assert mesh.devices.size == 8
        assert mesh.axis_names == ("data",)

    def test_2d_mesh(self):
        mesh = make_mesh({"data": 4, "model": 2})
        assert mesh.shape["data"] == 4
        assert mesh.shape["model"] == 2

    def test_bad_shape_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"data": 3})

    def test_can_shard_on_virtual_mesh(self):
        from nornicdb_tpu.parallel import can_shard

        assert can_shard() is True  # conftest forces 8 virtual devices


class TestShardedCorpus:
    def test_matches_single_device(self):
        mesh = make_mesh()
        sc = ShardedCorpus(dims=32, mesh=mesh, dtype=jnp.float32)
        dc = DeviceCorpus(dims=32)
        data = _rand(500, 32, 1)
        ids = [f"n{i}" for i in range(500)]
        sc.add_batch(ids, data)
        dc.add_batch(ids, data)
        q = data[123]
        got = sc.search(q, k=10)[0]
        want = dc.search(q, k=10)[0]
        assert [g[0] for g in got] == [w[0] for w in want]
        np.testing.assert_allclose(
            [g[1] for g in got], [w[1] for w in want], atol=2e-2
        )

    def test_self_query_top1(self):
        sc = ShardedCorpus(dims=16, mesh=make_mesh(), dtype=jnp.float32)
        data = _rand(300, 16, 2)
        sc.add_batch([f"n{i}" for i in range(300)], data)
        res = sc.search(data[77], k=3)
        assert res[0][0][0] == "n77"
        assert res[0][0][1] == pytest.approx(1.0, abs=1e-2)

    def test_remove_and_compact(self):
        sc = ShardedCorpus(dims=8, mesh=make_mesh(), dtype=jnp.float32,
                           compact_ratio=0.05)
        data = _rand(100, 8, 3)
        sc.add_batch([f"n{i}" for i in range(100)], data)
        for i in range(30):
            sc.remove(f"n{i}")
        res = sc.search(data[10], k=100)
        ids = {r[0] for r in res[0]}
        assert "n10" not in ids
        assert "n50" in ids
        assert len(sc) == 70

    def test_batch_queries(self):
        sc = ShardedCorpus(dims=16, mesh=make_mesh(), dtype=jnp.float32)
        data = _rand(256, 16, 4)
        sc.add_batch([f"n{i}" for i in range(256)], data)
        res = sc.search(data[:8], k=1)
        assert [r[0][0] for r in res] == [f"n{i}" for i in range(8)]

    def test_growth_keeps_shard_alignment(self):
        mesh = make_mesh()
        sc = ShardedCorpus(dims=8, mesh=mesh, dtype=jnp.float32)
        data = _rand(2000, 8, 5)
        sc.add_batch([f"n{i}" for i in range(2000)], data)
        assert sc.capacity % (128 * 8) == 0 or sc.capacity % np.lcm(128, 8) == 0
        res = sc.search(data[1999], k=1)
        assert res[0][0][0] == "n1999"


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        mesh = make_mesh({"seq": 8})
        b, t, h, dh = 2, 64, 4, 16  # t sharded 8 ways -> 8 per chip
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((b, t, h, dh)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((b, t, h, dh)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((b, t, h, dh)).astype(np.float32))
        ring = make_ring_attention(mesh, "seq", causal=causal)
        got = np.asarray(ring(q, k, v))
        want = np.asarray(reference_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)

    def test_long_sequence_memory_shape(self):
        # 8 chips x 32 tokens = 256-token sequence, each chip holds 32
        mesh = make_mesh({"seq": 8})
        ring = make_ring_attention(mesh, "seq", causal=True)
        q = jnp.ones((1, 256, 2, 8), jnp.float32)
        out = ring(q, q, q)
        assert out.shape == (1, 256, 2, 8)
        assert bool(jnp.all(jnp.isfinite(out)))


class TestShardedStreaming:
    def test_sharded_search_streaming_parity(self):
        """Per-shard streaming Pallas kernel inside shard_map must agree with
        the XLA per-shard path (top-1 identical on a well-separated corpus)."""
        from nornicdb_tpu.parallel.sharded_index import ShardedCorpus

        rng = np.random.default_rng(11)
        sc = ShardedCorpus(dims=64)
        vecs = rng.standard_normal((1024, 64)).astype(np.float32)
        sc.add_batch([f"v{i}" for i in range(1024)], vecs)
        q = vecs[42]
        a = sc.search(q, k=5, streaming="interpret")
        b = sc.search(q, k=5, streaming=False)
        assert a[0][0][0] == b[0][0][0] == "v42"
