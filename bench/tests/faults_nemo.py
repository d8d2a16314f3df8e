"""Break the Nemotron 3 Nano path underneath a run, then drive the whole run.

    python3 bench/tests/faults_nemo.py <fault> --workload nemo-chat-sys4k ...

As ``faults.py``, ``faults_dsv2.py``, ``faults_lcf.py`` and
``faults_cmda.py``: each fault alters the program where it computes (or
where it keeps a lane's state), and ``run.py`` has to come out with
``"correct": false``.  These ten are what a rank of this architecture, and
a scheduler that keeps a recurrent state beside its pages, can get wrong
and still stream plausible tokens: a snapshot registered under the boundary
a chunk later than the state it holds, the convolution's inputs dropped at
a prefix hit, a re-seated lane that reads what the slot's last holder left,
a padding row that advances the chunk lane's state, ``dt_bias`` left out,
the gates unscaled (2.5) or unnormalised, the shared expert or the held
experts' part dropped, rope applied in the attention layers.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def snapshot_a_chunk_early():
    """A snapshot is registered under the key of the boundary one chunk
    AFTER the one its state stands at (the state taken a chunk early for
    the boundary it is filed under): a hit that ends there begins from a
    state that lacks a chunk of tokens (the K/V pages are all there)."""
    from nornicdb_tpu.genserve.engine import GenerationEngine

    plain = GenerationEngine._snapshot

    def late_key(self, seq, end):
        keys, ps = seq.prefix_keys, self._page_size
        ahead = self._prefill_chunk // ps
        if keys is None or end % ps or end // ps - 1 + ahead >= len(keys):
            return plain(self, seq, end)
        seq.prefix_keys = keys[ahead:]  # boundary n reads key n + a chunk
        try:
            return plain(self, seq, end)
        finally:
            seq.prefix_keys = keys

    GenerationEngine._snapshot = late_key


def conv_state_dropped_at_a_hit():
    """A lane that reads another slot than it writes (a prefix hit taken up,
    a snapshot left or gone on from) reads the SSM state there and zeros for
    the convolution's last inputs."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import nemotron_h as m

    plain = m._lane_block

    def dropped(cfg, blk, state, at, xbc, dt, lane, slot, live, read, write):
        conv = state["conv"]
        moved = jnp.zeros((conv.shape[1],), bool).at[
            jnp.where(read != write, read, conv.shape[1])].set(
                True, mode="drop").at[0].set(False)
        y, new = plain(cfg, blk, {**state, "conv": jnp.where(
            moved[None, :, None, None], 0, conv)}, at, xbc, dt, lane, slot,
            live, read, write)
        # what was hidden from the step is as it was
        kept = jnp.where(moved[None, :, None, None], conv, new["conv"])
        written = jnp.zeros_like(moved).at[write].set(True).at[0].set(False)
        return y, {**new, "conv": jnp.where(
            written[None, :, None, None], new["conv"], kept)}

    m._lane_block = dropped


def state_not_reset_on_reseat():
    """A lane seated for a new sequence reads its own slot, as the slot's
    last holder left it, instead of the null slot or the snapshot its hit
    ends on."""
    from nornicdb_tpu.genserve.engine import GenerationEngine

    plain = GenerationEngine._admit

    def dirty(self):
        before = {id(s) for s in self._running}
        plain(self)
        for seq in self._running:
            if id(seq) in before or not seq.tables:
                continue
            for k, kind in enumerate(self._kinds):
                if kind.state:
                    seq.bases[k] = int(seq.tables[k][0])

    GenerationEngine._admit = dirty


def padding_row_advances():
    """The chunk block's rows that are no token (a chunk shorter than its
    bucket) advance the chunk lane's state like tokens."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import nemotron_h as m

    plain = m.state_rows

    def every_row(rows, read, write, lmax):
        out = plain(rows, read, write, lmax)
        if out.chunk is None:
            return out
        lane, slot, live, r, w = out.chunk
        return out._replace(chunk=(lane, slot, jnp.ones_like(live), r, w))

    m.state_rows = every_row


def dt_bias_left_out():
    """``dt = softplus(dt)``: every head's step is ~0.7, not 0.001-0.1."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import nemotron_h as m

    plain = m.mamba_layer.__wrapped__

    def bare(cfg, blk, lanes, x, state, at):
        return plain(cfg, {**blk, "dt_bias": jnp.zeros_like(blk["dt_bias"])},
                     lanes, x, state, at)

    m.mamba_layer = bare


def _route_with(gates_of):
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.models import nemotron_h as m

    plain = m.route

    def route(cfg, blk, x):
        ids, _ = plain(cfg, blk, x)
        s = jax.nn.sigmoid(jnp.einsum(
            "nh,he->ne", x.astype(jnp.float32),
            blk["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        return ids, gates_of(cfg, jnp.take_along_axis(s, ids, axis=-1))

    m.route = route


def gates_unscaled():
    """The gates are normalised over the chosen six and NOT multiplied by
    ``routed_scaling_factor``: 2.5 times too small."""
    _route_with(lambda cfg, s: s / s.sum(-1, keepdims=True))


def gates_unnormalised():
    """The gates are the sigmoid scores times the scaling factor, not their
    share of the chosen six's sum: about six times too large."""
    _route_with(lambda cfg, s: s * cfg.routed_scaling_factor)


def _experts_without(which: str):
    from nornicdb_tpu.models import nemotron_h as m

    plain = m.experts.held_experts

    def part(tree, x, weight):
        shared = tree["up"].shape[0] == 1 and weight.shape[1] == 1
        return plain(tree, x, weight * (shared != (which == "shared")))

    m.experts.held_experts = part


def shared_dropped():
    """The expert layer adds its routed sum only."""
    _experts_without("shared")


def held_dropped():
    """The expert layer adds its shared expert only: the held experts'
    matmul contributes nothing."""
    _experts_without("held")


def rope_in_attention():
    """The attention layers rotate q and k by position (half pairs, the
    config's unused ``rope_theta``), as every other family's do."""
    import jax.numpy as jnp

    from nornicdb_tpu.models import nemotron_h as m
    from nornicdb_tpu.models.layers import dense as plain

    def dense(p, x, rotate=None):
        y = plain(p, x)
        if rotate is None:
            return y
        cos, sin, d = rotate
        y3 = y.astype(jnp.float32).reshape(x.shape[0], -1, d)
        a, b = y3[..., :d // 2], y3[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).reshape(y.shape).astype(y.dtype)

    step = m.attend_step

    def rotated(cfg, blk, rows, pool, at, x):
        d = cfg.head_dim
        inv = 1.0 / 10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angles = rows.pos[:, None].astype(jnp.float32) * inv
        rot = (jnp.cos(angles)[:, None], jnp.sin(angles)[:, None], d)
        m.dense = lambda p, x: dense(p, x, rot if p is blk["q"]
                                     or p is blk["k"] else None)
        try:
            return step(cfg, blk, rows, pool, at, x)
        finally:
            m.dense = plain

    m.attend_step = rotated


FAULTS = {f.__name__: f for f in (
    snapshot_a_chunk_early, conv_state_dropped_at_a_hit,
    state_not_reset_on_reseat, padding_row_advances, dt_bias_left_out,
    gates_unscaled, gates_unnormalised, shared_dropped, held_dropped,
    rope_in_attention)}

if __name__ == "__main__":
    FAULTS[sys.argv.pop(1)]()
    import run

    run.main()
