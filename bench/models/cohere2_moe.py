"""Model family ``cohere2_moe``: Command A+'s language model (window layers
beside full ones, a parallel block, a sigmoid router over 128 experts, four
shared experts averaged) behind the assistant, served by genserve as ONE of
the ranks that share each layer by expert parallelism: the configuration
says which routed experts are held here, how many layers and which slice of
the vocabulary.

The same parts as every family file (see ``bge_m3.py``, ``qwen2.py``,
``deepseek_v2.py``, ``longcat_flash.py``).  Below ``install`` nothing
imports the program or takes anything it made.

The reference is the decoder as published (``config.json``, ``model_type``
``cohere2_moe``): for a layer with stream ``x`` (a parallel block; every
layer is an expert layer)::

    h      = LN(x)                  (x - mean) / sqrt(var + eps) * g, no bias
    q,k,v  = h Wq, h Wk, h Wv       128 / 8 / 8 heads of 128
    sliding_attention (layers 0, 1, 2 of each 4): q, k rotated over
        interleaved pairs (2i, 2i + 1), theta 50000, all 128 dims; query i
        sees keys j with i - 4096 < j <= i
    full_attention (layer 3 of each 4): no rotation; query i sees every
        j <= i
    a      = softmax(q k^T / sqrt(128)) v Wo
    s      = sigmoid(h Wr); the top-8 of s; g_i = s_i / sum of the chosen
    x'     = x + a + sum_i g_i E_i(h) + 1/4 sum_j S_j(h)

``logits = LN_f(x) E^T * logit_scale`` over the tied table (its held
slice); a loop over the HELD experts (what the absent ones would add is
left out, as in the program).  Float32 at ``highest`` matmul precision, one
sequence at a time, one layer at a time, attention one K/V group (16 query
heads) at a time and in blocks of queries, the experts one at a time: no
cache, no batching.  Read where the config does not settle it: "average" =
the mean of the four shared experts' outputs; no routed scaling factor, no
router bias; the window's edge ``i - j < sliding_window``.  Departure from
the checkpoint: an expert's three matrices stacked.
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
SLIDING = "sliding_attention"
# the reference runs beside the deployment's 13.1 GB: a block's scores are
# (16 heads, block, T) float32, 59 MB at T = 7,168; a group's q and output
# 59 MB each, its two weight slices 67 MB; an expert's three casts 0.2 GB
QUERY_BLOCK = 128
ROW_SLAB = 2048   # rows of the routed comparison's readings at a time
ATTN_SLAB = 256   # and of their attention: (16 heads, rows, T) f32 scores


# ------------------------------------------------------- the program's side
def program_config(spec: dict):
    # the program's module FIRST: a commit that has none ends here, before
    # any weights are made
    from nornicdb_tpu.models import cohere2_moe

    fields = cohere2_moe.Cohere2MoeConfig.__dataclass_fields__
    sizes = {k: v for k, v in spec.items() if k in fields}
    sizes.update(num_hidden_layers=spec["num_layers"],
                 num_experts=spec["router_outputs"],
                 held_experts=tuple(spec["held_experts"]),
                 layer_types=tuple(spec["layer_types"]),
                 rope_theta=float(spec["rope_theta"]))
    cfg = cohere2_moe.Cohere2MoeConfig(**sizes)
    if spec["num_experts"] != spec["held_experts"][1]:
        sys.exit("num_experts states the experts held here: "
                 f"{spec['num_experts']} != {spec['held_experts'][1]}")
    if spec.get("preset"):
        preset = getattr(cohere2_moe, spec["preset"])
        if cfg != preset:
            sys.exit(f"sizes differ from the serve preset {spec['preset']}: "
                     f"{cfg} != {preset}")
    return cfg


def install(db, app_cfg, spec: dict, params):
    """What ``db.heimdall`` wires for a weights-backed assistant of any
    decoder family (``bench/models/deepseek_v2.py`` ``install``): the
    generator handed to ``db.set_heimdall_generator``, so that
    ``_wire_genserve`` builds the GenerationEngine (which resolves the
    family, and its page kinds, from the config's type); then the engine's
    own warm-up of every program class, as ``cmd_serve`` calls it at
    boot."""
    from nornicdb_tpu.heimdall.manager import WeightsGenerator
    from nornicdb_tpu.models.tokenizer import HashTokenizer

    db.set_heimdall_generator(WeightsGenerator(
        cfg=program_config(spec), params=params,
        tokenizer=HashTokenizer(spec["vocab_size"]),
        max_context=spec["max_context"]))
    engine = db.genserve_engine()
    if engine is None:
        sys.exit("db.set_heimdall_generator built no generation engine "
                 "(genserve.enabled is off?)")
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
def make_params(spec: dict, seed: int) -> dict:
    """Seeded weights in the served dtype, made on the device a LAYER at a
    time: every matrix N(0, 1/fan_in), so queries, keys, values, scores and
    the residual stream are O(1) at every depth; the (tied) token table
    0.02; norm scales 1 + 0.1 N(0,1), so leaving one out shows; the
    router's rows N(0, router_logit_std^2 / hidden): over unit-RMS rows the
    128 logits of a row spread by ``router_logit_std``, which sets how
    uneven a row's eight gates are (they sum to 1 whatever it is)."""
    import jax
    import jax.numpy as jnp

    h, width = spec["hidden_size"], spec["intermediate_size"]
    hq = spec["num_attention_heads"] * spec["head_dim"]
    hkv = spec["num_key_value_heads"] * spec["head_dim"]
    dt = jnp.dtype(spec["dtype"])

    def mat(k, *shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def scale(k, n):
        return {"scale": 1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)}

    def mlp(k, count):
        k = jax.random.split(k, 3)
        return {"gate": mat(k[0], count, h, width, std=h ** -0.5),
                "up": mat(k[1], count, h, width, std=h ** -0.5),
                "down": mat(k[2], count, width, h, std=width ** -0.5)}

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 8)
        return {
            "norm": scale(k[0], h),
            "q": {"w": mat(k[1], h, hq, std=h ** -0.5)},
            "k": {"w": mat(k[2], h, hkv, std=h ** -0.5)},
            "v": {"w": mat(k[3], h, hkv, std=h ** -0.5)},
            "o": {"w": mat(k[4], hq, h, std=hq ** -0.5)},
            "router": mat(k[5], h, spec["router_outputs"],
                          std=spec["router_logit_std"] * h ** -0.5),
            "experts": mlp(k[6], spec["held_experts"][1]),
            "shared": mlp(k[7], spec["num_shared_experts"])}

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"tok_emb": mat(k[0], spec["vocab_size"], h, std=0.02),
                "final_norm": scale(k[1], h)}

    keys = jax.random.split(jax_key(seed + 2), spec["num_layers"] + 1)
    params = ends(keys[0])
    params["blocks"] = [layer(keys[1 + li])
                        for li in range(spec["num_layers"])]
    return params


def _attention_params(spec: dict) -> int:
    h, d = spec["hidden_size"], spec["head_dim"]
    return 2 * h * spec["num_attention_heads"] * d \
        + 2 * h * spec["num_key_value_heads"] * d


def _expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["intermediate_size"]


def _layer_outside_experts(spec: dict) -> int:
    """A layer's matrices outside its routed experts: attention, the shared
    experts, the router."""
    return (_attention_params(spec)
            + spec["num_shared_experts"] * _expert_params(spec)
            + spec["hidden_size"] * spec["router_outputs"])


def _outside_experts(spec: dict) -> int:
    """Matrix parameters held here outside the routed experts: the above
    for every layer and the (tied) token table."""
    return spec["num_layers"] * _layer_outside_experts(spec) \
        + spec["vocab_size"] * spec["hidden_size"]


def matrix_params(spec: dict) -> int:
    return _outside_experts(spec) + spec["num_layers"] \
        * spec["held_experts"][1] * _expert_params(spec)


def param_bytes(spec: dict) -> int:
    """The matrices in the served dtype; in float32 the norm scales (one a
    layer and the final one)."""
    return matrix_params(spec) * BYTES_OF[spec["dtype"]] \
        + (spec["num_layers"] + 1) * spec["hidden_size"] * 4


def kv_page_counts(spec: dict, options: dict) -> dict:
    """Pages of each kind's pool as the engine sizes them from the model's
    config and the deployment's options (``hbm_reckoning`` is held to
    this): the full kind ``pool_pages``; the window kind ``max_seqs x
    (pages of window + prefill_chunk, and one) + one context's``, and the
    null page."""
    ps = options["genserve.page_size"]
    context = -(-options["genserve.max_seq_tokens"] // ps)
    lane = -(-(spec["sliding_window"] + options.get(
        "genserve.prefill_chunk", 64)) // ps) + 1
    return {"full": options["genserve.pool_pages"],
            "window": min(options["genserve.pool_pages"] - 1,
                          options["genserve.max_seqs"] * min(lane, context)
                          + context) + 1}


# ------------------------------------------------------------- reference
@functools.lru_cache(maxsize=4)
def _programs(shape: tuple, mode: str):
    """``shape`` = (heads, kv heads, head_dim, eps, window, experts a token,
    shared experts, first held expert, logit scale)."""
    import jax
    import jax.numpy as jnp

    (heads, groups, d, eps, window, top_k, n_shared, first,
     logit_scale) = shape
    rep = heads // groups
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def mm(x, w, spec="ti,io->to"):
        w = w.astype(f32)
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
        return jnp.einsum(spec, x, w, precision=hi)

    def ln(p, x):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(f32)

    def rope(x, cos, sin):
        """x (T, n, d); cos, sin (T, d/2): pair i is columns (2i, 2i+1)."""
        x0, x1 = x[..., 0::2], x[..., 1::2]
        c, s = cos[:, None], sin[:, None]
        return jnp.stack([x0 * c - x1 * s, x1 * c + x0 * s], -1).reshape(
            x.shape)

    def swiglu(w, x):
        return mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])

    def by_group(blk):
        """(Wq, Wo) one K/V group's query heads at a time."""
        h = blk["q"]["w"].shape[0]
        return (jnp.moveaxis(blk["q"]["w"].reshape(h, groups, rep * d), 1, 0),
                blk["o"]["w"].reshape(groups, rep * d, h))

    def keys_values(blk, h, cos, sin, sliding):
        t = h.shape[0]
        k = mm(h, blk["k"]["w"]).reshape(t, groups, d)
        if sliding:
            k = rope(k, cos, sin)
        return k, mm(h, blk["v"]["w"]).reshape(t, groups, d)

    @functools.partial(jax.jit, static_argnames=("sliding",))
    def attention(blk, h, cos, sin, sliding):
        """Normed rows h (T, hidden) of one sequence at positions 0 .. T-1
        -> (attention through W_o (T, hidden), its keys (T, groups, d)
        as attended, its values)."""
        t = h.shape[0]
        k, v = keys_values(blk, h, cos, sin, sliding)
        at_k = jnp.arange(t)

        def group(args):
            wq, wo, kg, vg = args
            q = mm(h, wq).reshape(t, rep, d)
            if sliding:
                q = rope(q, cos, sin)

            def block(args):  # one block of queries against every key
                qb, lo = args
                at_q = (lo + jnp.arange(QUERY_BLOCK))[:, None]
                seen = at_k[None, :] <= at_q
                if sliding:
                    seen = seen & (at_q - at_k[None, :] < window)
                s = jnp.einsum("qrd,kd->rqk", qb, kg, precision=hi) \
                    / np.sqrt(d)
                p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), -1)
                return jnp.einsum("rqk,kd->qrd", p, vg, precision=hi)

            o = jax.lax.map(block, (q.reshape(-1, QUERY_BLOCK, rep, d),
                                    jnp.arange(0, t, QUERY_BLOCK)))
            return mm(o.reshape(t, rep * d), wo)

        wq, wo = by_group(blk)
        out, _ = jax.lax.scan(
            lambda acc, args: (acc + group(args), None), jnp.zeros_like(h),
            (wq, wo, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        return out, k, v

    @functools.partial(jax.jit, static_argnames=("sliding",))
    def attention_rows(blk, h, at, cos, sin, k_all, v_all, sliding):
        """:func:`attention` for SOME rows of the sequence, each perhaps
        changed upstream: row i stands at position ``at[i]`` and sees the
        sequence's own keys and values before it (``k_all``, ``v_all``:
        what :func:`attention` returned for this layer) and itself."""
        n = h.shape[0]
        k_own, v_own = keys_values(blk, h, cos, sin, sliding)
        at_k = jnp.arange(k_all.shape[0])
        seen = at_k[None, :] < at[:, None]
        if sliding:
            seen = seen & (at[:, None] - at_k[None, :] < window)

        def group(args):
            wq, wo, kg, vg, ko, vo = args
            q = mm(h, wq).reshape(n, rep, d)
            if sliding:
                q = rope(q, cos, sin)
            s = jnp.einsum("qrd,kd->rqk", q, kg, precision=hi) / np.sqrt(d)
            own = jnp.einsum("qrd,qd->rq", q, ko, precision=hi) / np.sqrt(d)
            p = jax.nn.softmax(jnp.concatenate(
                [jnp.where(seen[None], s, -1e30), own[..., None]], -1), -1)
            o = jnp.einsum("rqk,kd->qrd", p[..., :-1], vg, precision=hi) \
                + jnp.moveaxis(p[..., -1], 0, 1)[..., None] * vo[:, None]
            return mm(o.reshape(n, rep * d), wo)

        wq, wo = by_group(blk)
        move = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        return jax.lax.scan(
            lambda acc, args: (acc + group(args), None), jnp.zeros_like(h),
            (wq, wo, move(k_all), move(v_all), move(k_own), move(v_own)))[0]

    def scores(blk, h):
        # the router, in float32 in every mode (its stated precision)
        return jnp.einsum("th,he->te", h, blk["router"].astype(f32),
                          precision=hi)

    def experts(blk, h, held_gates):
        """The held experts under ``held_gates`` (rows, held), one at a
        time, + the mean of the shared experts, one at a time."""
        def routed(acc, args):
            g, w = args
            return acc + g[:, None] * swiglu(w, h), None

        def shared(acc, w):
            return acc + swiglu(w, h), None

        out, _ = jax.lax.scan(routed, jnp.zeros_like(h),
                              (held_gates.T, blk["experts"]))
        mean, _ = jax.lax.scan(shared, jnp.zeros_like(h), blk["shared"])
        return out + mean / n_shared

    @jax.jit
    def expert_layer(blk, h):
        """``routed + shared`` of normed rows h under the reference's own
        routing: the ``top_k`` best of the sigmoid scores, gates normalised
        over the chosen."""
        s = jax.nn.sigmoid(scores(blk, h))
        top, ids = jax.lax.top_k(s, top_k)
        gates = top / top.sum(-1, keepdims=True)
        held = blk["experts"]["gate"].shape[0]
        on_held = ids[..., None] == first + jnp.arange(held)
        return experts(blk, h, jnp.sum(
            jnp.where(on_held, gates[..., None], 0.0), 1))

    @jax.jit
    def normed(blk, x):
        return ln(blk["norm"], x)

    @jax.jit
    def row_scores(blk, h):
        return scores(blk, h)

    @jax.jit
    def row_experts(blk, h, held_gates):
        return experts(blk, h, held_gates)

    @jax.jit
    def head(final_norm, table, x_rows):
        return mm(ln(final_norm, x_rows), table, "th,vh->tv") * logit_scale

    return (attention, expert_layer, head, attention_rows, row_scores,
            row_experts, normed)


def _shape_of(spec: dict) -> tuple:
    return (spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], float(spec["layer_norm_eps"]),
            spec["sliding_window"], spec["num_experts_per_tok"], spec["num_shared_experts"],
            spec["held_experts"][0], float(spec.get("logit_scale", 1.0)))


def _rotary(spec: dict, positions):
    """(n, head_dim / 2) cos and sin at ``positions``: angles in float64."""
    d = spec["head_dim"]
    inv = 1.0 / float(spec["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.outer(np.asarray(positions, np.float64), inv)
    return (np.cos(angles).astype(np.float32),
            np.sin(angles).astype(np.float32))


def _forward(spec: dict, params: dict, ids: list[int], mode: str,
             pad_to: int = 0):
    """ONE sequence through every layer with no cache, padded on the right
    (causal, so the padding is never seen) to a multiple of the query block
    or to ``pad_to``.  Returns the last stream rows and, for each layer,
    its keys and values as attended ``(k, v)``."""
    import jax.numpy as jnp

    attention, expert_layer, _, _, _, _, normed = \
        _programs(_shape_of(spec), mode)
    t = max(len(ids), pad_to)
    t += -t % QUERY_BLOCK
    padded = np.zeros((t,), np.int32)
    padded[:len(ids)] = ids
    cos, sin = _rotary(spec, np.arange(t))
    x = params["tok_emb"][padded].astype(jnp.float32)
    cached = []
    for blk, kind in zip(params["blocks"], spec["layer_types"], strict=True):
        h = normed(blk, x)
        a, k, v = attention(blk, h, cos, sin, sliding=kind == SLIDING)
        x = x + a + expert_layer(blk, h)
        cached.append((k, v))
    return x, cached


def reference_logits(spec: dict, params: dict, ids: list[int], rows,
                     mode: str = "highest", pad_to: int = 0) -> np.ndarray:
    """(len(rows), vocab) float32 logits at positions ``rows`` of ONE
    sequence (:func:`_forward`)."""
    head = _programs(_shape_of(spec), mode)[2]
    x = _forward(spec, params, ids, mode, pad_to)[0]
    return np.asarray(head(params["final_norm"], params["tok_emb"],
                           x[np.asarray(rows, np.int32)]))


# A routed layer is discontinuous where scores tie: the served program reads
# the router's input in bfloat16 and may then keep another expert than the
# float32 reference, rightly.  So the reference answers for EVERY routing
# that its own scores allow once each router logit may move by half of
# ROUTE_TIE, in units of the row's spread of logits over all experts (the
# router is linear in its input, so a relative error of the input moves each
# logit in proportion to that spread; ``bench/models/deepseek_v2.py`` has
# the argument and the measurement behind 2^-3).  A sigmoid keeps the
# logits' order, so this router's one edge, the 8th against the 9th, is read
# on the logits; the gates are normalised over the chosen eight, so a
# routing that differs on ANY expert moves the held experts' gates.
ROUTE_TIE = 2.0 ** -3
# at most this many readings of one row, the likeliest first
ROW_READINGS = 16
_EDGE = 3   # logits looked at on each side of the edge


def _tops(logits: np.ndarray, m: int, tie: float) -> list[tuple]:
    """(how far logits have to move, indices) of every set of ``m`` entries
    that is the top ``m`` of ``logits`` once each may move by ``tie / 2``:
    the plain top ``m`` first, then by need.  Of the kept ones only the last
    ``_EDGE`` may go, of the dropped ones only the first ``_EDGE`` may
    come."""
    order = np.argsort(-logits, kind="stable")
    top, rest = order[:m], order[m:]
    if not len(rest):
        return [(-np.inf, top)]
    out_able = [i for i in top[-_EDGE:] if logits[i] - logits[rest[0]] <= tie]
    in_able = [j for j in rest[:_EDGE] if logits[top[-1]] - logits[j] <= tie]
    sure = [i for i in top if i not in out_able]
    edge = out_able + in_able
    sets = []
    for kept in itertools.combinations(edge, len(out_able)):
        dropped = [j for j in edge if j not in kept]
        need = max((logits[j] for j in dropped), default=-np.inf) \
            - min((logits[i] for i in kept), default=np.inf)
        if need <= tie:
            sets.append((need, np.asarray(sure + list(kept), np.int64)))
    return sorted(sets, key=lambda s: s[0])


def held_gate_choices(spec: dict, z: np.ndarray) -> list[list[np.ndarray]]:
    """For each row of router logits ``z`` (rows, experts): the gates of the
    HELD experts (held,) under the reference's routing, then under every
    other routing within ROUTE_TIE (:func:`_tops`) that gives them other
    gates, the likeliest first.  Plain numpy: the same top-k of sigmoid
    scores, normalised over the chosen, as ``expert_layer``, written
    again."""
    k = spec["num_experts_per_tok"]
    first, held = spec["held_experts"]
    z64 = z.astype(np.float64)
    s = 1.0 / (1.0 + np.exp(-z64))
    tie = ROUTE_TIE * z64.std(axis=1)
    out = []
    for r in range(z.shape[0]):
        choices = []
        for _, ids in _tops(z64[r], k, tie[r]):
            gates = np.zeros(held, np.float32)
            here = (ids >= first) & (ids < first + held)
            gates[ids[here] - first] = s[r, ids[here]] / s[r, ids].sum()
            if not any(np.array_equal(gates, c) for c in choices):
                choices.append(gates)
        out.append(choices)
    return out


def _row_readings(spec: dict, params: dict, ids, cached, positions):
    """Logits of the rows at ``positions`` of the sequence ``ids``, whose
    layers left the keys and values ``cached`` (:func:`_forward`), under
    every routing that :func:`held_gate_choices` allows them, layer after
    layer (a row that took another expert in one layer goes on from there,
    against the sequence's own keys and values: what one row's other
    routing does to LATER rows through their attention is left out, as for
    the other routed families).  The block is parallel: a row's attention
    and shared average are computed once a reading that came in, and its
    readings part at the routed sum.  Returns (logits (readings, vocab), the
    row of ``positions`` each reading is of); a row's first reading is the
    reference's own."""
    _, _, head, attention_rows, row_scores, row_experts, normed = \
        _programs(_shape_of(spec), "highest")
    of = np.arange(len(positions))
    at = np.asarray(positions, np.int32)
    x = np.asarray(params["tok_emb"][np.asarray(ids, np.int32)[at]],
                   np.float32)

    def slabs(fn, *rows, slab=ROW_SLAB):
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

    for blk, kind, (k_all, v_all) in zip(params["blocks"],
                                         spec["layer_types"], cached,
                                         strict=True):
        sliding = kind == SLIDING
        cos, sin = _rotary(spec, at[of])
        h = slabs(lambda r: normed(blk, r), x)
        a = slabs(lambda r, p, c, s: attention_rows(
            blk, r, p, c, s, k_all, v_all, sliding=sliding),
            h, at[of], cos, sin, slab=ATTN_SLAB)
        z = slabs(lambda r: row_scores(blk, r), h)
        readings = np.bincount(of, minlength=len(at))
        parent, gates = [], []
        for i, options in enumerate(held_gate_choices(spec, z)):
            room = ROW_READINGS - readings[of[i]]
            options = options[:1 + max(0, min(len(options) - 1, room))]
            readings[of[i]] += len(options) - 1
            parent += [i] * len(options)
            gates += options
        x = (x + a)[parent] + slabs(lambda r, g: row_experts(blk, r, g),
                                    h[parent], np.stack(gates))
        of = of[parent]
    logits = slabs(lambda r: head(params["final_norm"], params["tok_emb"], r),
                   x)
    return logits, of


def greedy_gaps(spec: dict, params: dict, sequences: list, control: bool):
    """For each ``(prompt ids, produced ids)``: at every produced position
    the reference's best logit minus the reference's logit of the token that
    was served (0 wherever the served token is the reference's argmax);
    where the reference's routing of that row stands on an edge
    (:data:`ROUTE_TIE`), the least such gap over the routings the edge
    allows, each computed by the reference alone.  With ``control`` also the
    same gap for the token that the fp8 forward of the same prompt and
    tokens puts first.  Returns (gaps, control gaps), one array a
    sequence."""
    pad_to = max(len(p) + len(o) for p, o in sequences)
    gaps, low = [], []

    def least(logits, of, tokens):
        gap = logits.max(axis=1) - logits[np.arange(len(of)), tokens[of]]
        out = np.full(len(tokens), np.inf, np.float32)
        np.minimum.at(out, of, gap)
        return out

    for prompt, out in sequences:
        ids = list(prompt) + list(out[:-1])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        cached = _forward(spec, params, ids, "highest", pad_to)[1]
        logits, of = _row_readings(spec, params, ids, cached, rows)
        served = np.clip(np.asarray(out, np.int64), 0, logits.shape[1] - 1)
        gaps.append(least(logits, of, served))
        if control:
            first = reference_logits(spec, params, ids, rows, mode="fp8",
                                     pad_to=pad_to).argmax(axis=1)
            low.append(least(logits, of, first))
    return gaps, low


# ------------------------------------------------------------------ work
def _held_share(spec: dict) -> float:
    """Routed experts a token meets HERE, a layer: ``top-k x held /
    published`` (0.5 of the 8 for 8 of 128), the expectation under even
    routing; what a run really routed here is ``routed_here_share``."""
    return spec["num_experts_per_tok"] * spec["held_experts"][1] \
        / spec["router_outputs"]


def matmul_params_per_token(spec: dict) -> float:
    """Parameters a token multiplies against on this chip's share, outside
    the head: attention, the four shared experts and the router of every
    layer, the routed experts at the expected number met here."""
    return spec["num_layers"] * (
        _layer_outside_experts(spec)
        + _held_share(spec) * _expert_params(spec))


def _layers_by_kind(spec: dict) -> tuple:
    """(full layers, window layers)."""
    n_window = sum(1 for kind in spec["layer_types"] if kind == SLIDING)
    return len(spec["layer_types"]) - n_window, n_window


def kv_bytes_per_token_layer(spec: dict) -> int:
    """A token's K and V rows in one layer."""
    return 2 * spec["num_key_value_heads"] * spec["head_dim"] \
        * BYTES_OF[spec["dtype"]]


def _seen(spec: dict, lo: int, hi: int) -> float:
    """Cached rows the tokens at positions lo..hi-1 see, summed over the
    layers: a full layer all ``p + 1`` of them, a window layer ``min(p + 1,
    sliding_window)``."""
    n_full, n_window = _layers_by_kind(spec)
    w = spec["sliding_window"]
    ramp = lambda a, b: (b - a) * (a + b + 1) / 2.0  # noqa: E731
    edge = min(max(lo, w), hi)  # the first position that sees a whole window
    return n_full * ramp(lo, hi) \
        + n_window * (ramp(lo, edge) if lo < edge else 0.0) \
        + n_window * (hi - edge) * w


def _span_tokens(prefill_spans, decode_spans) -> float:
    return sum(share * (hi - lo) for share, lo, hi in prefill_spans) \
        + sum(hi - lo for lo, hi in decode_spans)


def _flops(spec: dict, prefill_spans, decode_spans, sampled: float) -> float:
    """2 FLOPs a parameter a token (above), QK^T and PV over the rows the
    token sees (4 x heads x head_dim a row a layer: a window layer's rows
    end at the window), and one row of the head over the held vocabulary
    for each token that was sampled."""
    per_tok = 2.0 * matmul_params_per_token(spec)
    attn = 4.0 * spec["num_attention_heads"] * spec["head_dim"]
    flops = 0.0
    for share, lo, hi in prefill_spans:  # positions lo..hi-1, a share of it
        flops += share * (per_tok * (hi - lo) + attn * _seen(spec, lo, hi))
    for lo, hi in decode_spans:          # positions lo..hi-1, one a step
        flops += per_tok * (hi - lo) + attn * _seen(spec, lo, hi)
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
    once, and of each layer the held experts that at least one of its rows
    is routed to: with R rows a run (the tokens over the runs) and even
    routing, ``held x (1 - (1 - top-k / published)^R)`` of them.  Each
    decoded token reads the K and V rows it sees, by kind: a full layer's
    up to its own, a window layer's up to the window.  The FLOPs are the
    tokens' own.  For ``step_roofline``."""
    spec = config["generator"]
    flops = _flops(spec, prefill_spans, decode_spans, sampled)
    if not flops or not executions:
        return {"flops": 0.0, "bytes": 0.0}
    rows = _span_tokens(prefill_spans, decode_spans) / executions
    hit = spec["held_experts"][1] * (
        1.0 - (1.0 - spec["num_experts_per_tok"]
               / spec["router_outputs"]) ** rows)
    weights = (_outside_experts(spec) + spec["num_layers"] * hit
               * _expert_params(spec)) * BYTES_OF[spec["dtype"]]
    cached = sum(_seen(spec, lo, hi) for lo, hi in decode_spans)
    return {"flops": flops, "bytes": executions * weights
            + cached * kv_bytes_per_token_layer(spec)}
