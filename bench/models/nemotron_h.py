"""Model family ``nemotron_h``: Nemotron 3 Nano's language model (a stack
whose every layer is ONE mixer: a Mamba-2 layer, an expert layer of ungated
relu^2 experts with one shared, or grouped-query attention without
positions) behind the assistant, served by genserve as ONE of the ranks
that share each layer by expert parallelism: the configuration says which
routed experts are held here, how many layers and which slice of the
vocabulary.

The same parts as every family file (see ``bge_m3.py``, ``qwen2.py``,
``deepseek_v2.py``, ``longcat_flash.py``, ``cohere2_moe.py``).  Below
``install`` nothing imports the program or takes anything it made.

The reference is the decoder as published (``config.json``, ``model_type``
``nemotron_h``): a layer ``l`` with stream ``x`` is ``x + mixer_l(RMSNorm_l
(x))``, the mixer by ``hybrid_override_pattern[l]``::

    M  [z | xBC | dt] = x W_in           4096 | 6144 | 64
       xBC_t = silu(sum_{j<4} w_j xBC_{t-3+j} + b)   depthwise, causal, the
                                         inputs before the sequence zero
       [x' | B | C] = xBC                4096 | 8 x 128 | 8 x 128
       dt = softplus(dt + dt_bias), A = -exp(A_log), head h of group h // 8:
       S_h <- exp(dt_h A_h) S_h + dt_h x'_h (x) B_g;  y_h = S_h C_g + D_h x'_h
       out = RMSNorm_grouped(y * silu(z)) W_out      groups of 512
    E  s = sigmoid(x W_r); the top-6 of s + bias; g_i = s_i / sum of the
       chosen x 2.5; sum_i g_i E_i(x) + S(x), E(x) = W_down relu(W_up x)^2
    *  softmax(q k^T / sqrt(128)) v W_o, 32 heads over 2 K/V heads, causal,
       no positions

``logits = RMSNorm_f(x) W_head`` over the untied head (its held slice); a
loop over the HELD experts (what the absent ones would add is left out, as
in the program).  Float32 at ``highest`` matmul precision, one sequence at
a time, one layer at a time, the Mamba layer as the SEQUENTIAL recurrence
from a zero state (one token after the other: no chunked form, no state
kept between calls), attention one K/V group at a time and in blocks of
queries, the experts one at a time: no cache, no batching.  Read where the
config does not settle it: no position embedding (``rope_theta`` and
``partial_rotary_factor`` are unused by the family's own code); ``dt`` not
clamped; the router without groups (``n_group`` 1).  Departure from the
checkpoint: an expert's two matrices stacked.
"""

from __future__ import annotations

import functools
import itertools
import os
import sys

import numpy as np

from reference import fp8, hash_word_ids as tokenize, jax_key
from work import BYTES_OF

ROLE = "generator"
HERE = os.path.dirname(os.path.abspath(__file__))
EOS = 2
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the reference runs beside the deployment's 13.3 GB: a block's scores are
# (16 heads, block, T) float32, 59 MB at T = 7,168; the states the produced
# rows go on from are (rows, 64, 64, 128) float32, 268 MB for 128 rows of
# ONE layer, kept a layer at a time
QUERY_BLOCK = 128
ROW_SLAB = 2048   # rows of the routed comparison's readings at a time
ATTN_SLAB = 256   # and of their attention: (16 heads, rows, T) f32 scores
STATE_SLAB = 64   # and of their Mamba step: 2 MB of state a reading


# ------------------------------------------------------- the program's side
def program_config(spec: dict):
    # the program's module FIRST: a commit that has none ends here, before
    # any weights are made
    from nornicdb_tpu.models import nemotron_h

    fields = nemotron_h.NemotronHConfig.__dataclass_fields__
    sizes = {k: v for k, v in spec.items() if k in fields}
    sizes.update(num_hidden_layers=spec["num_layers"],
                 n_routed_experts=spec["router_outputs"],
                 held_experts=tuple(spec["held_experts"]))
    cfg = nemotron_h.NemotronHConfig(**sizes)
    if spec["n_routed_experts"] != spec["held_experts"][1]:
        sys.exit("n_routed_experts states the experts held here: "
                 f"{spec['n_routed_experts']} != {spec['held_experts'][1]}")
    if spec.get("preset"):
        preset = getattr(nemotron_h, spec["preset"])
        if cfg != preset:
            sys.exit(f"sizes differ from the serve preset {spec['preset']}: "
                     f"{cfg} != {preset}")
    return cfg


def install(db, app_cfg, spec: dict, params):
    """What ``db.heimdall`` wires for a weights-backed assistant of any
    decoder family (``bench/models/deepseek_v2.py`` ``install``): the
    generator handed to ``db.set_heimdall_generator``, so that
    ``_wire_genserve`` builds the GenerationEngine (which resolves the
    family, and its kinds of cache state, from the config's type); then the
    engine's own warm-up of every program class, as ``cmd_serve`` calls it
    at boot."""
    from nornicdb_tpu.heimdall.manager import WeightsGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer

    db.set_heimdall_generator(WeightsGenerator(
        cfg=program_config(spec), params=params,
        tokenizer=HashTokenizer(spec["vocab_size"]),
        max_context=spec["max_context"]))
    engine = db.genserve_engine()
    if engine is None:
        sys.exit("db.set_heimdall_generator built no generation engine")
    engine.warmup(timeout=float(spec.get("warmup_timeout_s", 1100.0)))
    return engine


# -------------------------------------------------- tokenizer and prompt
@functools.lru_cache(maxsize=4)
def _head_ids(preamble_file: str, template: str, system: str,
              vocab_size: int) -> tuple:
    with open(os.path.join(os.path.dirname(HERE), preamble_file)) as f:
        preamble = f.read()
    return tuple(tokenize(template.format(preamble=preamble, system=system),
                          vocab_size))


def prompt_ids(spec: dict, request: dict) -> list[int]:
    """The token ids the engine is handed for one chat request: the
    deployment's prompt format (the assistant's own preamble, each message
    as ``role: content``, then ``assistant:``), tokenized word by word over
    the HELD slice of the vocabulary, the tail kept where it passes
    ``max_context``."""
    p = spec["prompt"]
    head = _head_ids(p["preamble_file"], p["head"], request["system"],
                     spec["vocab_size"])
    tail = tokenize(p["tail"].format(user=request["user"]),
                    spec["vocab_size"])
    return (list(head) + tail)[-spec["max_context"]:]


def shared_prefix_tokens(spec: dict, request: dict) -> int:
    """Tokens every request of the run shares (the preamble and the system
    message): any implementation has to prefill only what follows them."""
    p = spec["prompt"]
    return len(_head_ids(p["preamble_file"], p["head"], request["system"],
                         spec["vocab_size"]))


# --------------------------------------------------------------- weights
def _sizes(spec: dict) -> tuple:
    """(d_inner, conv_dim, Mamba heads)."""
    d_inner = spec["mamba_num_heads"] * spec["mamba_head_dim"]
    return (d_inner, d_inner + 2 * spec["n_groups"] * spec["ssm_state_size"],
            spec["mamba_num_heads"])


def make_params(spec: dict, seed: int) -> dict:
    """Seeded weights in the served dtype, made on the device a LAYER at a
    time: every matrix N(0, 1/fan_in) (the convolution's four taps too), so
    the mixers' outputs and the residual stream are O(1) at every depth; the
    token table 0.02, the head N(0, 1/hidden), untied; norm scales (the
    layers', the gated one, the final one) 1 + 0.1 N(0,1), so leaving one
    out shows; ``A_log = log(1 .. heads)``, ``D = 1``, ``dt_bias`` the
    inverse softplus of a log-uniform draw in [time_step_min,
    time_step_max] floored at time_step_floor (Mamba-2's own
    initialisation: heads remember from one to about a thousand tokens); a
    0.1 N(0,1) convolution bias; the router's rows N(0, router_logit_std^2
    / hidden) and a ``router_bias_std`` N(0,1) ``e_score_correction_bias``
    (it moves the choice, never the gates)."""
    import jax
    import jax.numpy as jnp

    h = spec["hidden_size"]
    hq = spec["num_attention_heads"] * spec["head_dim"]
    hkv = spec["num_key_value_heads"] * spec["head_dim"]
    d_inner, conv_dim, heads = _sizes(spec)
    taps = spec["conv_kernel"]
    dt = jnp.dtype(spec["dtype"])
    f32 = jnp.float32

    def mat(k, *shape, std):
        return (jax.random.normal(k, shape, f32) * std).astype(dt)

    def scale(k, n):
        return {"scale": 1.0 + 0.1 * jax.random.normal(k, (n,), f32)}

    def mlp(k, count, width):
        k = jax.random.split(k, 2)
        return {"up": mat(k[0], count, h, width, std=h ** -0.5),
                "down": mat(k[1], count, width, h, std=width ** -0.5)}

    @jax.jit
    def mamba(key):
        k = jax.random.split(key, 7)
        lo, hi = np.log(spec["time_step_min"]), np.log(spec["time_step_max"])
        step = jnp.maximum(jnp.exp(jax.random.uniform(k[4], (heads,), f32)
                                   * (hi - lo) + lo),
                           spec["time_step_floor"])
        return {
            "norm": scale(k[0], h),
            "in_proj": {"w": mat(k[1], h, d_inner + conv_dim + heads,
                                 std=h ** -0.5)},
            "conv": {"w": mat(k[2], taps, conv_dim, std=taps ** -0.5),
                     "b": 0.1 * jax.random.normal(k[3], (conv_dim,), f32)},
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=f32)),
            "D": jnp.ones((heads,), f32),
            "gate_norm": scale(k[5], d_inner),
            "out_proj": {"w": mat(k[6], d_inner, h, std=d_inner ** -0.5)}}

    @jax.jit
    def experts(key):
        k = jax.random.split(key, 5)
        return {
            "norm": scale(k[0], h),
            "router": mat(k[1], h, spec["router_outputs"],
                          std=spec["router_logit_std"] * h ** -0.5),
            "router_bias": spec["router_bias_std"] * jax.random.normal(
                k[2], (spec["router_outputs"],), f32),
            "experts": mlp(k[3], spec["held_experts"][1],
                           spec["moe_intermediate_size"]),
            "shared": mlp(k[4], 1,
                          spec["moe_shared_expert_intermediate_size"])}

    @jax.jit
    def attention(key):
        k = jax.random.split(key, 5)
        return {"norm": scale(k[0], h),
                "q": {"w": mat(k[1], h, hq, std=h ** -0.5)},
                "k": {"w": mat(k[2], h, hkv, std=h ** -0.5)},
                "v": {"w": mat(k[3], h, hkv, std=h ** -0.5)},
                "o": {"w": mat(k[4], hq, h, std=hq ** -0.5)}}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 3)
        return {"tok_emb": mat(k[0], spec["vocab_size"], h, std=0.02),
                "lm_head": {"w": mat(k[1], h, spec["vocab_size"],
                                     std=h ** -0.5)},
                "final_norm": scale(k[2], h)}

    make = {MAMBA: mamba, EXPERTS: experts, ATTENTION: attention}
    keys = jax.random.split(jax_key(seed + 2), spec["num_layers"] + 1)
    params = ends(keys[0])
    params["blocks"] = [make[mixer](keys[1 + li]) for li, mixer in
                        enumerate(spec["hybrid_override_pattern"])]
    return params


def _count(spec: dict, mixer: str) -> int:
    return spec["hybrid_override_pattern"].count(mixer)


def _mamba_params(spec: dict) -> int:
    """A Mamba layer's matrices: in_proj, the convolution's taps, out_proj."""
    d_inner, conv_dim, heads = _sizes(spec)
    return spec["hidden_size"] * (d_inner + conv_dim + heads) \
        + spec["conv_kernel"] * conv_dim + d_inner * spec["hidden_size"]


def _attention_params(spec: dict) -> int:
    h, d = spec["hidden_size"], spec["head_dim"]
    return 2 * h * spec["num_attention_heads"] * d \
        + 2 * h * spec["num_key_value_heads"] * d


def _expert_params(spec: dict) -> int:
    return 2 * spec["hidden_size"] * spec["moe_intermediate_size"]


def _expert_layer_outside(spec: dict) -> int:
    """An expert layer's matrices outside its routed experts: the shared
    expert and the router."""
    return 2 * spec["hidden_size"] \
        * spec["moe_shared_expert_intermediate_size"] \
        + spec["hidden_size"] * spec["router_outputs"]


def _outside_experts(spec: dict) -> int:
    """Matrix parameters held here outside the routed experts: every
    layer's own, the token table and the head."""
    return _count(spec, MAMBA) * _mamba_params(spec) \
        + _count(spec, ATTENTION) * _attention_params(spec) \
        + _count(spec, EXPERTS) * _expert_layer_outside(spec) \
        + 2 * spec["vocab_size"] * spec["hidden_size"]


def matrix_params(spec: dict) -> int:
    return _outside_experts(spec) + _count(spec, EXPERTS) \
        * spec["held_experts"][1] * _expert_params(spec)


def param_bytes(spec: dict) -> int:
    """The matrices in the served dtype; in float32 the norm scales (one a
    layer and the final one), and of a Mamba layer the gated norm's, the
    convolution's bias, dt_bias, A_log and D, of an expert layer the
    router's bias."""
    d_inner, conv_dim, heads = _sizes(spec)
    small = (spec["num_layers"] + 1) * spec["hidden_size"] \
        + _count(spec, MAMBA) * (d_inner + conv_dim + 3 * heads) \
        + _count(spec, EXPERTS) * spec["router_outputs"]
    return matrix_params(spec) * BYTES_OF[spec["dtype"]] + small * 4


def state_slot_bytes(spec: dict) -> int:
    """One lane's recurrent state over the Mamba layers: the SSM state in
    float32, the convolution's last inputs in the served dtype."""
    _, conv_dim, heads = _sizes(spec)
    return _count(spec, MAMBA) * (
        heads * spec["mamba_head_dim"] * spec["ssm_state_size"] * 4
        + (spec["conv_kernel"] - 1) * conv_dim * BYTES_OF[spec["dtype"]])


def pool_bytes(spec: dict, options: dict) -> dict:
    """Bytes of each kind's pool as the engine sizes them from the model's
    config and the deployment's options (``hbm_reckoning`` is held to
    this): K/V pages for the attention layers, state slots for the Mamba
    layers."""
    row = spec["num_key_value_heads"] * spec["head_dim"] \
        * BYTES_OF[spec["dtype"]]
    return {"full": _count(spec, ATTENTION) * 2
            * options["genserve.pool_pages"] * options["genserve.page_size"]
            * row,
            "state": options["genserve.state_slots"] * state_slot_bytes(spec)}


# ------------------------------------------------------------- reference
@functools.lru_cache(maxsize=4)
def _programs(shape: tuple, mode: str):
    """``shape`` = (heads, kv heads, head_dim, eps, Mamba heads, Mamba head
    dim, groups, state, taps, experts a token, first held expert, routed
    scaling factor)."""
    import jax
    import jax.numpy as jnp

    (heads, groups, d, eps, m_heads, p, m_groups, n, taps, top_k, first,
     scaling) = shape
    rep = heads // groups
    d_inner = m_heads * p
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def mm(x, w, spec="ti,io->to", wide=False):
        w = w.astype(f32)
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
        elif mode == "bf16":  # the served precision: a matmul's input and,
            x = _bf16(x)      # but for the head's logits (wide), its output
        y = jnp.einsum(spec, x, w, precision=hi)
        return _bf16(y) if mode == "bf16" and not wide else y

    def rms(scale, x):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def relu2(w, x):
        return mm(jnp.square(jax.nn.relu(mm(x, w["up"]))), w["down"])

    # ---- attention (no positions)
    def by_group(blk):
        """(Wq, Wo) one K/V group's query heads at a time."""
        h = blk["q"]["w"].shape[0]
        return (jnp.moveaxis(blk["q"]["w"].reshape(h, groups, rep * d), 1, 0),
                blk["o"]["w"].reshape(groups, rep * d, h))

    def keys_values(blk, h):
        t = h.shape[0]
        return (mm(h, blk["k"]["w"]).reshape(t, groups, d),
                mm(h, blk["v"]["w"]).reshape(t, groups, d))

    @jax.jit
    def attention(blk, h):
        """Normed rows h (T, hidden) of one sequence at positions 0 .. T-1
        -> (attention through W_o (T, hidden), its keys (T, groups, d), its
        values)."""
        t = h.shape[0]
        k, v = keys_values(blk, h)
        at_k = jnp.arange(t)

        def group(args):
            wq, wo, kg, vg = args
            q = mm(h, wq).reshape(t, rep, d)

            def block(args):  # one block of queries against every key
                qb, lo = args
                at_q = (lo + jnp.arange(QUERY_BLOCK))[:, None]
                s = jnp.einsum("qrd,kd->rqk", qb, kg, precision=hi) \
                    / np.sqrt(d)
                prob = jax.nn.softmax(
                    jnp.where((at_k[None, :] <= at_q)[None], s, -1e30), -1)
                return jnp.einsum("rqk,kd->qrd", prob, vg, precision=hi)

            o = jax.lax.map(block, (q.reshape(-1, QUERY_BLOCK, rep, d),
                                    jnp.arange(0, t, QUERY_BLOCK)))
            return mm(o.reshape(t, rep * d), wo)

        wq, wo = by_group(blk)
        out, _ = jax.lax.scan(
            lambda acc, args: (acc + group(args), None), jnp.zeros_like(h),
            (wq, wo, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        return out, k, v

    @jax.jit
    def attention_rows(blk, h, at, k_all, v_all):
        """:func:`attention` for SOME rows of the sequence, each perhaps
        changed upstream: row i stands at position ``at[i]`` and sees the
        sequence's own keys and values before it and itself."""
        rows = h.shape[0]
        k_own, v_own = keys_values(blk, h)
        seen = jnp.arange(k_all.shape[0])[None, :] < at[:, None]

        def group(args):
            wq, wo, kg, vg, ko, vo = args
            q = mm(h, wq).reshape(rows, rep, d)
            s = jnp.einsum("qrd,kd->rqk", q, kg, precision=hi) / np.sqrt(d)
            own = jnp.einsum("qrd,qd->rq", q, ko, precision=hi) / np.sqrt(d)
            prob = jax.nn.softmax(jnp.concatenate(
                [jnp.where(seen[None], s, -1e30), own[..., None]], -1), -1)
            o = jnp.einsum("rqk,kd->qrd", prob[..., :-1], vg, precision=hi) \
                + jnp.moveaxis(prob[..., -1], 0, 1)[..., None] * vo[:, None]
            return mm(o.reshape(rows, rep * d), wo)

        wq, wo = by_group(blk)
        move = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        return jax.lax.scan(
            lambda acc, args: (acc + group(args), None), jnp.zeros_like(h),
            (wq, wo, move(k_all), move(v_all), move(k_own), move(v_own)))[0]

    # ---- the Mamba layer, sequentially
    def projected(blk, h):
        proj = mm(h, blk["in_proj"]["w"])
        return (proj[:, :d_inner], proj[:, d_inner:-m_heads],
                jax.nn.softplus(proj[:, -m_heads:] + blk["dt_bias"]))

    def convolved(blk, window):
        """window (rows, taps, conv_dim): a row's own input last -> its
        x' (rows, heads, p), B and C (rows, heads, n: a head reads its
        group's)."""
        w = blk["conv"]["w"].astype(f32)
        xbc = jax.nn.silu(jnp.einsum("rjc,jc->rc", window, w, precision=hi)
                          + blk["conv"]["b"])
        rows = xbc.shape[0]
        spread = lambda a: jnp.repeat(  # noqa: E731
            a.reshape(rows, m_groups, n), m_heads // m_groups, axis=1)
        return (xbc[:, :d_inner].reshape(rows, m_heads, p),
                spread(xbc[:, d_inner:d_inner + m_groups * n]),
                spread(xbc[:, d_inner + m_groups * n:]))

    def advance(blk, s, x_t, b_t, dt_t):
        a = -jnp.exp(blk["A_log"])
        return jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]

    def gated(blk, y, x, z):
        rows = y.shape[0]
        y = (y + blk["D"][:, None] * x).reshape(rows, d_inner) \
            * jax.nn.silu(z)
        y = rms(jnp.ones(()), y.reshape(rows, m_groups, -1)).reshape(
            rows, d_inner) * blk["gate_norm"]["scale"]
        return mm(y, blk["out_proj"]["w"])

    @functools.partial(jax.jit, static_argnames=("keep",))
    def mamba(blk, h, first_row, keep):
        """Normed rows h (T, hidden) of one sequence from a zero state, one
        token after the other -> (the mixer's output (T, hidden); for the
        ``keep`` positions from ``first_row`` on, what a row there goes on
        from: the ``taps - 1`` inputs before it (keep, taps - 1, conv_dim)
        and the state before it (keep, heads, p, n))."""
        t = h.shape[0]
        z, xbc, dt = projected(blk, h)
        padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
        window = jnp.stack([padded[j:j + t] for j in range(taps)], axis=1)
        x, b, c = convolved(blk, window)

        def token(carry, row):
            s, kept = carry
            x_t, b_t, c_t, dt_t, at = row
            # the state BEFORE this token, where a kept row stands here; a
            # row that is not kept writes the spare last slot
            slot = jnp.where((at >= first_row) & (at < first_row + keep),
                             at - first_row, keep)
            kept = jax.lax.dynamic_update_slice(kept, s[None],
                                                (slot, 0, 0, 0))
            s = advance(blk, s, x_t, b_t, dt_t)
            return (s, kept), (s * c_t[:, None, :]).sum(-1)

        zero = jnp.zeros((m_heads, p, n))
        (_, kept), y = jax.lax.scan(
            token, (zero, jnp.zeros((keep + 1,) + zero.shape)),
            (x, b, c, dt, jnp.arange(t)))
        before = first_row + jnp.arange(keep)[:, None] + jnp.arange(taps - 1)
        return gated(blk, y, x, z), padded[before], kept[:keep]

    @jax.jit
    def mamba_rows(blk, h, of, inputs, states):
        """:func:`mamba` for SOME rows of the sequence, each perhaps changed
        upstream: reading i is of kept row ``of[i]`` and goes on from the
        sequence's own inputs ``inputs[of[i]]`` (taps - 1, conv_dim) and
        state ``states[of[i]]`` before it (both stay on the device)."""
        z, xbc, dt = projected(blk, h)
        x, b, c = convolved(blk, jnp.concatenate(
            [inputs[of], xbc[:, None]], 1))
        s = advance(blk, states[of], x, b, dt)
        return gated(blk, (s * c[:, :, None, :]).sum(-1), x, z)

    # ---- the expert layer
    def scores(blk, h):
        # the router, in float32 in every mode (its stated precision)
        return jnp.einsum("th,he->te", h, blk["router"].astype(f32),
                          precision=hi)

    def experts(blk, h, held_gates):
        """The held experts under ``held_gates`` (rows, held), one at a
        time, + the shared expert."""
        def routed(acc, args):
            g, w = args
            return acc + g[:, None] * relu2(w, h), None

        out, _ = jax.lax.scan(routed, jnp.zeros_like(h),
                              (held_gates.T, blk["experts"]))
        return out + relu2(jax.tree.map(lambda a: a[0], blk["shared"]), h)

    @jax.jit
    def expert_layer(blk, h, ids=None):
        """``routed + shared`` of normed rows h under the reference's own
        routing: the ``top_k`` best of sigmoid score + bias, gates the
        scores normalised over the chosen, times the scaling factor.  Given
        ``ids`` (rows, top_k), those experts are the chosen ones (another
        forward's choice: the gates are still this one's scores)."""
        s = jax.nn.sigmoid(scores(blk, h))
        if ids is None:
            _, ids = jax.lax.top_k(s + blk["router_bias"], top_k)
        top = jnp.take_along_axis(s, ids, axis=-1)
        gates = top / top.sum(-1, keepdims=True) * scaling
        held = blk["experts"]["up"].shape[0]
        on_held = ids[..., None] == first + jnp.arange(held)
        return experts(blk, h, jnp.sum(
            jnp.where(on_held, gates[..., None], 0.0), 1)), ids

    @jax.jit
    def normed(blk, x):
        return rms(blk["norm"]["scale"], x)

    @jax.jit
    def row_scores(blk, h):
        return scores(blk, h)

    @jax.jit
    def row_experts(blk, h, held_gates):
        return experts(blk, h, held_gates)

    @jax.jit
    def head(final_norm, w, x_rows):
        return mm(rms(final_norm["scale"], x_rows), w, "th,hv->tv", wide=True)

    return (attention, expert_layer, head, attention_rows, row_scores,
            row_experts, normed, mamba, mamba_rows)


def _bf16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _shape_of(spec: dict) -> tuple:
    return (spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], float(spec["norm_eps"]),
            spec["mamba_num_heads"], spec["mamba_head_dim"],
            spec["n_groups"], spec["ssm_state_size"], spec["conv_kernel"],
            spec["num_experts_per_tok"], spec["held_experts"][0],
            float(spec["routed_scaling_factor"]))


def _forward(spec: dict, params: dict, ids: list[int], mode: str,
             pad_to: int = 0, rows=None, routing=None):
    """ONE sequence through every layer with no cache, padded on the right
    (causal, so the padding is never seen) to a multiple of the query block
    or to ``pad_to``.  Yields, a layer, ``(mixer, blk, what a row of
    ``rows`` (consecutive positions) needs of the sequence to go through
    this layer by itself)``: an attention layer's keys and values, a Mamba
    layer's inputs and states before the rows (an expert layer's: the
    experts each row chose); at the end ``(None, None, the last stream
    rows)``.  ``routing``: the experts every row is to choose, an expert
    layer after the other (what another forward yielded)."""
    import jax.numpy as jnp

    (attention, expert_layer, _, _, _, _, normed, mamba, _) = \
        _programs(_shape_of(spec), mode)
    t = max(len(ids), pad_to)
    t += -t % QUERY_BLOCK
    padded = np.zeros((t,), np.int32)
    padded[:len(ids)] = ids
    first, keep = (int(rows[0]), len(rows)) if rows is not None else (0, 0)
    x = params["tok_emb"][padded].astype(jnp.float32)
    routing = iter(routing or ())
    for blk, mixer in zip(params["blocks"], spec["hybrid_override_pattern"],
                          strict=True):
        h = normed(blk, x)
        if mixer == MAMBA:
            out, inputs, states = mamba(blk, h, first, keep=keep)
            kept = (inputs, states)
        elif mixer == ATTENTION:
            out, k, v = attention(blk, h)
            kept = (k, v)
        else:
            out, kept = expert_layer(blk, h, next(routing, None))
        x = x + out
        if mode == "bf16":  # the served program keeps its stream in bf16
            x = _bf16(x)
        yield mixer, blk, kept
    yield None, None, x


def reference_logits(spec: dict, params: dict, ids: list[int], rows,
                     mode: str = "highest", pad_to: int = 0,
                     routing=None) -> np.ndarray:
    """(len(rows), vocab) float32 logits at positions ``rows`` of ONE
    sequence (:func:`_forward`)."""
    head = _programs(_shape_of(spec), mode)[2]
    *_, (_, _, x) = _forward(spec, params, ids, mode, pad_to,
                             routing=routing)
    return np.asarray(head(params["final_norm"], params["lm_head"]["w"],
                           x[np.asarray(rows, np.int32)]))


# A routed layer is discontinuous where scores tie: the served program reads
# the router's input in bfloat16 and may then keep another expert than the
# float32 reference, rightly.  So the reference answers for EVERY routing
# that its own scores allow once each router logit may move by half of
# ROUTE_TIE, in units of the row's spread of logits over all experts (the
# router is linear in its input, so a relative error of the input moves each
# logit in proportion to that spread; ``bench/models/deepseek_v2.py`` has
# the argument and the measurement behind 2^-3).  The choice is by sigmoid
# score + bias: read on ``logit + logit(bias's effect)`` it is no longer the
# logits' order, so the edge, the 6th against the 7th, is read on the
# CHOICE scores themselves, their tie in units of the spread of logits times
# the sigmoid's slope at the edge.  The gates are normalised over the chosen
# six, so a routing that differs on ANY expert moves the held experts' gates:
# by a hair where two ABSENT experts trade places (the chosen scores all
# stand at 0.93-0.999, so the sum moves by a thousandth).  Eleven expert
# layers deep, a reading for every such hair would spend a row's readings
# before the routings that matter: held gates that agree to bfloat16's
# resolution (GATE_SAME, relative) are ONE reading.
ROUTE_TIE = 2.0 ** -3
GATE_SAME = 2.0 ** -8
# at most this many readings of one row: the likeliest, by how far the
# scores had to move for them, summed over the layers behind (a beam: eleven
# expert layers deep a row has more routings within the tie than can be
# read, and the ones the served program really takes need the least).  Read
# on the chip on one run's streams (PERF.md section 6, PR 41): a tie of 2^-3
# with 16 readings reads a largest gap of 0.760 in 38 s, 2^-2 with 16 0.760
# in 73 s, 2^-2 with 64 0.646 in 204 s, 2^-1 with 32 0.694 in 244 s: what
# is left is not this row's routing but the rows BEFORE it (a row that took
# another expert feeds the next rows' convolution and state), which no
# reading of one row reaches
ROW_READINGS = 16
_EDGE = 3   # scores looked at on each side of the edge


def _tops(scores: np.ndarray, m: int, tie: float) -> list[tuple]:
    """(how far scores have to move, indices) of every set of ``m`` entries
    that is the top ``m`` of ``scores`` once each may move by ``tie / 2``:
    the plain top ``m`` first, then by need.  Of the kept ones only the last
    ``_EDGE`` may go, of the dropped ones only the first ``_EDGE`` may
    come."""
    order = np.argsort(-scores, kind="stable")
    top, rest = order[:m], order[m:]
    if not len(rest):
        return [(-np.inf, top)]
    out_able = [i for i in top[-_EDGE:] if scores[i] - scores[rest[0]] <= tie]
    in_able = [j for j in rest[:_EDGE] if scores[top[-1]] - scores[j] <= tie]
    sure = [i for i in top if i not in out_able]
    edge = out_able + in_able
    sets = []
    for kept in itertools.combinations(edge, len(out_able)):
        dropped = [j for j in edge if j not in kept]
        need = max((scores[j] for j in dropped), default=-np.inf) \
            - min((scores[i] for i in kept), default=np.inf)
        if need <= tie:
            sets.append((need, np.asarray(sure + list(kept), np.int64)))
    return sorted(sets, key=lambda s: s[0])


def held_gate_choices(spec: dict, z: np.ndarray,
                      bias: np.ndarray) -> list[list[np.ndarray]]:
    """:func:`held_gate_options` without how far each lies."""
    return [[gates for _, gates in options]
            for options in held_gate_options(spec, z, bias)]


def held_gate_options(spec: dict, z: np.ndarray,
                      bias: np.ndarray) -> list[list[tuple]]:
    """For each row of router logits ``z`` (rows, experts): (how far the
    scores have to move, as a share of the tie: 0 for the reference's own
    routing; the gates of the HELD experts (held,)) under the reference's
    routing, then under every other routing within ROUTE_TIE (:func:`_tops`)
    that gives them other gates, the likeliest first.  Plain numpy: the same
    top-k of sigmoid score + bias, the scores normalised over the chosen and
    scaled, as ``expert_layer``, written again."""
    k = spec["num_experts_per_tok"]
    first, held = spec["held_experts"]
    z64 = z.astype(np.float64)
    s = 1.0 / (1.0 + np.exp(-z64))
    choice = s + bias.astype(np.float64)
    out = []
    for r in range(z.shape[0]):
        # a logit's tie as a tie of scores: times the sigmoid's slope at
        # the edge (the k-th chosen score)
        edge = np.sort(s[r])[-k]
        tie = ROUTE_TIE * z64[r].std() * edge * (1.0 - edge)
        choices = []
        for need, ids in _tops(choice[r], k, tie):
            gates = np.zeros(held, np.float32)
            here = (ids >= first) & (ids < first + held)
            gates[ids[here] - first] = s[r, ids[here]] / s[r, ids].sum() \
                * spec["routed_scaling_factor"]
            if not any(np.allclose(gates, c, rtol=GATE_SAME, atol=0.0)
                       for _, c in choices):
                choices.append((max(float(need), 0.0) / max(tie, 1e-30),
                                gates))
        out.append(choices)
    return out


def _slabs(fn, *rows, slab=ROW_SLAB):
    """``fn`` over ``slab`` rows at a time, the last slab padded on the
    host: one shape a function, small temporaries."""
    out = []
    for lo in range(0, len(rows[0]), slab):
        part = [a[lo:lo + slab] for a in rows]
        n = len(part[0])
        part = [np.concatenate([a, np.repeat(a[:1], slab - n, 0)])
                for a in part]
        out.append(np.asarray(fn(*part))[:n])
    return np.concatenate(out)


def _row_readings(spec: dict, params: dict, ids, positions, pad_to: int):
    """Logits of the rows at ``positions`` (consecutive) of the sequence
    ``ids`` under every routing that :func:`held_gate_choices` allows them,
    layer after layer, beside the sequence's own forward (:func:`_forward`,
    a layer at a time: a row that took another expert in one layer goes on
    from there, against the sequence's own keys and values, and from the
    sequence's own convolution inputs and state before it: what one row's
    other routing does to LATER rows through their attention and their
    state is left out, as for the other routed families).  Returns (logits
    (readings, vocab), the row of ``positions`` each reading is of); a
    row's first reading is the reference's own."""
    (_, _, head, attention_rows, row_scores, row_experts, normed, _,
     mamba_rows) = _programs(_shape_of(spec), "highest")
    of = np.arange(len(positions))
    moved = np.zeros(len(positions))  # how far each reading's scores moved
    at = np.asarray(positions, np.int32)
    x = np.asarray(params["tok_emb"][np.asarray(ids, np.int32)[at]],
                   np.float32)
    for mixer, blk, kept in _forward(spec, params, ids, "highest", pad_to,
                                     rows=at):
        if mixer is None:
            break
        h = _slabs(lambda r: normed(blk, r), x)
        if mixer == ATTENTION:
            k_all, v_all = kept
            x = x + _slabs(lambda r, p: attention_rows(
                blk, r, p, k_all, v_all), h, at[of], slab=ATTN_SLAB)
        elif mixer == MAMBA:
            x = x + _slabs(lambda r, i: mamba_rows(blk, r, i, *kept),
                           h, of.astype(np.int32), slab=STATE_SLAB)
        else:
            z = _slabs(lambda r: row_scores(blk, r), h)
            found = [(moved[i] + need, i, gates)
                     for i, options in enumerate(held_gate_options(
                         spec, z, np.asarray(blk["router_bias"])))
                     for need, gates in options]
            # a row keeps its ROW_READINGS likeliest (a stable sort: the
            # reference's own path, which moved nothing, stays first)
            found.sort(key=lambda f: (of[f[1]], f[0]))
            kept, seen = [], np.zeros(len(at), np.int64)
            for f in found:
                if seen[of[f[1]]] < ROW_READINGS:
                    seen[of[f[1]]] += 1
                    kept.append(f)
            parent = [i for _, i, _ in kept]
            moved = np.asarray([cost for cost, _, _ in kept])
            gates = np.stack([g for _, _, g in kept])
            x = x[parent] + _slabs(lambda r, g: row_experts(blk, r, g),
                                   h[parent], gates)
            of = of[parent]
    logits = _slabs(lambda r: head(params["final_norm"],
                                   params["lm_head"]["w"], r), x)
    return logits, of


def position_gaps(spec: dict, params: dict, sequences: list,
                  controls: tuple = ()):
    """For each ``(prompt ids, produced ids)``: at every produced position
    the reference's best logit minus the reference's logit of the token that
    was served (0 wherever the served token is the reference's argmax);
    where the reference's routing of that row stands on an edge
    (:data:`ROUTE_TIE`), the least such gap over the routings the edge
    allows, each computed by the reference alone.  For each mode of
    ``controls`` (``fp8``; ``bf16``, the served precision, for a witness of
    what rounding alone reads: PERF.md section 6) also the same gap for the
    token that the forward of the same prompt and tokens in that precision
    puts first; ``<mode>+routing``: that forward with every row's experts
    as the float32 forward chose them (what is left once no row takes
    another expert).  Returns (gaps, {mode: gaps}), one array a sequence."""
    pad_to = max(len(p) + len(o) for p, o in sequences)
    gaps, low = [], {mode: [] for mode in controls}

    def least(logits, of, tokens):
        gap = logits.max(axis=1) - logits[np.arange(len(of)), tokens[of]]
        out = np.full(len(tokens), np.inf, np.float32)
        np.minimum.at(out, of, gap)
        return out

    for prompt, out in sequences:
        ids = list(prompt) + list(out[:-1])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        logits, of = _row_readings(spec, params, ids, rows, pad_to)
        served = np.clip(np.asarray(out, np.int64), 0, logits.shape[1] - 1)
        gaps.append(least(logits, of, served))
        for mode in controls:
            routing = None
            if mode.endswith("+routing"):  # the float32 forward's choices
                routing = [kept for mixer, _, kept in _forward(
                    spec, params, ids, "highest", pad_to) if mixer == EXPERTS]
            first = reference_logits(
                spec, params, ids, rows, mode=mode.split("+")[0],
                pad_to=pad_to, routing=routing).argmax(axis=1)
            low[mode].append(least(logits, of, first))
    return gaps, low


def greedy_gaps(spec: dict, params: dict, sequences: list, control: bool):
    """What the comparison reads of :func:`position_gaps`
    (``bench/ops/chat_stream.py`` takes the largest entry over the
    sequences and holds it to ``greedy_gap_max``): ONE number for the run,
    the MEAN gap over every produced position of every sequence, given once
    a sequence; with ``control`` the same for the fp8 forward's tokens.

    Why the mean and not the largest gap, as for the other families: this
    stack is 27 layers deep with 11 routed ones and a recurrence.  The
    served bfloat16 program and the float32 reference keep another expert in
    about one row in twenty (a router input on a tie: rightly), and through
    the next rows' convolution and state that row's changed stream reaches
    the rows BEHIND it, which no reading of one row's routings covers.  So a
    sound run has a few positions in a hundred with gaps of 0.1-1.0 and
    nought elsewhere (the reference itself in bfloat16 reads the same, and
    nought once its rows are given the float32 forward's experts:
    ``bench/tests/nemo_gaps.py``, PERF.md section 6, PR 41), and its LARGEST
    gap says how unlucky the worst such row was, not how the program
    computes.  What a lower precision or a wrong step changes is how MANY
    positions stand off the reference's choice and by how much: the mean."""
    gaps, low = position_gaps(spec, params, sequences,
                              ("fp8",) if control else ())

    def run_mean(rows):
        return [np.full(1, np.concatenate(rows).mean(), np.float32)] \
            * len(rows)

    return run_mean(gaps), run_mean(low["fp8"]) if control else []


# ------------------------------------------------------------------ work
def _held_share(spec: dict) -> float:
    """Routed experts a token meets HERE, an expert layer: ``top-k x held /
    published`` (0.75 of the 6 for 16 of 128), the expectation under even
    routing; what a run really routed here is ``routed_here_share``."""
    return spec["num_experts_per_tok"] * spec["held_experts"][1] \
        / spec["router_outputs"]


def matmul_params_per_token(spec: dict) -> float:
    """Parameters a token multiplies against on this chip's share, outside
    the head: every Mamba and attention layer's matrices, the shared expert
    and the router of every expert layer, the routed experts at the expected
    number met here."""
    return _count(spec, MAMBA) * _mamba_params(spec) \
        + _count(spec, ATTENTION) * _attention_params(spec) \
        + _count(spec, EXPERTS) * (
            _expert_layer_outside(spec)
            + _held_share(spec) * _expert_params(spec))


def scan_flops_per_token(spec: dict) -> float:
    """The recurrence itself, a token a Mamba layer: decay, outer product
    and read-out over (heads, head dim, state), 2 FLOPs each."""
    return 6.0 * spec["mamba_num_heads"] * spec["mamba_head_dim"] \
        * spec["ssm_state_size"]


def kv_bytes_per_token_layer(spec: dict) -> int:
    """A token's K and V rows in one attention layer."""
    return 2 * spec["num_key_value_heads"] * spec["head_dim"] \
        * BYTES_OF[spec["dtype"]]


def _seen(lo: int, hi: int) -> float:
    """Cached rows the tokens at positions lo..hi-1 see in ONE attention
    layer: all ``p + 1`` of them."""
    return (hi - lo) * (lo + hi + 1) / 2.0


def _span_tokens(prefill_spans, decode_spans) -> float:
    return sum(share * (hi - lo) for share, lo, hi in prefill_spans) \
        + sum(hi - lo for lo, hi in decode_spans)


def _flops(spec: dict, prefill_spans, decode_spans, sampled: float) -> float:
    """2 FLOPs a parameter a token (above), the recurrence of every Mamba
    layer, QK^T and PV over the rows the token sees in every attention
    layer (4 x heads x head_dim a row), and one row of the head over the
    held vocabulary for each token that was sampled."""
    per_tok = 2.0 * matmul_params_per_token(spec) \
        + _count(spec, MAMBA) * scan_flops_per_token(spec)
    attn = 4.0 * spec["num_attention_heads"] * spec["head_dim"] \
        * _count(spec, ATTENTION)
    flops = 0.0
    for share, lo, hi in prefill_spans:  # positions lo..hi-1, a share of it
        flops += share * (per_tok * (hi - lo) + attn * _seen(lo, hi))
    for lo, hi in decode_spans:          # positions lo..hi-1, one a step
        flops += per_tok * (hi - lo) + attn * _seen(lo, hi)
    return flops + 2.0 * spec["vocab_size"] * spec["hidden_size"] * sampled


def gen_tokens(config: dict, prefill_spans, decode_spans, sampled) -> dict:
    """The tokens prefilled and decoded, whatever implements them: their
    FLOPs on this chip's share, and as bytes the held weights once (any
    number of tokens can share one read).  For ``step_mfu``: it cannot pass
    100 %."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    return {"flops": flops,
            "bytes": float(param_bytes(spec)) if flops else 0.0}


def fused_steps(config: dict, executions: int, prefill_spans, decode_spans,
                sampled) -> dict:
    """``executions`` runs of the fused step that carried these tokens
    between them.  Each run reads the weights outside the routed experts
    once, and of each expert layer the held experts that at least one of
    its rows is routed to: with R rows a run (the tokens over the runs) and
    even routing, ``held x (1 - (1 - top-k / published)^R)`` of them.  Each
    decoded token reads the K and V rows it sees in the attention layers,
    and reads and writes its lane's recurrent state once (a decode step
    cannot do with less); a prompt's prefilled part reads and writes its
    lane's state at least once, whatever the number of chunks.  The FLOPs
    are the tokens' own.  For ``step_roofline``."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    if not flops or not executions:
        return {"flops": 0.0, "bytes": 0.0}
    rows = _span_tokens(prefill_spans, decode_spans) / executions
    hit = spec["held_experts"][1] * (
        1.0 - (1.0 - spec["num_experts_per_tok"]
               / spec["router_outputs"]) ** rows)
    weights = (_outside_experts(spec) + _count(spec, EXPERTS) * hit
               * _expert_params(spec)) * BYTES_OF[spec["dtype"]]
    cached = _count(spec, ATTENTION) * sum(
        _seen(lo, hi) for lo, hi in decode_spans)
    lane_steps = sum(hi - lo for lo, hi in decode_spans) \
        + sum(share for share, _, _ in prefill_spans)
    return {"flops": flops, "bytes": executions * weights
            + cached * kv_bytes_per_token_layer(spec)
            + 2.0 * lane_steps * state_slot_bytes(spec)}
