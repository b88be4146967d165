"""Plain reference of the command-a-plus-05-2026 forward pass: jax.numpy, float32, no cache.

One file, no import from ``k_llms_tpu``: Cohere's parallel block written
straight from the equations in ``benchmark/configs/command-a-plus.json``'s
``source`` and ``assumed``. For every layer ``n = LN(x)`` and ``x <- x +
Attn_l(n) + MoE(n)``: one norm, two branches on the same normed input, one
residual add. ``LN`` is the mean-centred LayerNorm without bias. ``Attn_l`` is
grouped-query attention without bias or qk-norm; a ``sliding_attention`` layer
lets query ``i`` see keys ``(i - sliding_window, i]`` under RoPE in the
published interleaved form (pairs ``(2j, 2j + 1)``, ``rope_gptj``), a
``full_attention`` layer sees every earlier key and has no positional
embedding. ``MoE`` is the sigmoid router's top-k (weights normalised over the
chosen) over SwiGLU experts, plus the shared experts averaged. Tied embeddings:
the logits are ``LN_f(x) . E^T x logit_scale``. No cache, no kernel, no
batching: one sequence, attention in blocks of queries and one kv head's group
of query heads at a time (7k positions x 128 heads do not fit otherwise), every
held expert by a Python loop. The configuration is the published
``config.json`` as a dict (its own key names); the parameters are the
program's tree (``layers`` a list of per-layer dicts), upcast to float32 one
use at a time so that the full-width cut fits one chip.

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise computed in bfloat16 passes.

Departures from the published description, each also under ``assumed`` in the
configuration's file:

- *A share of the experts.* ``experts_held = (offset, count)``: the router is
  as wide as ``w_router`` (the published 128), chooses and normalises over all
  of them, and only the chosen experts in ``[offset, offset + count)``, whose
  weights ``params`` holds as stacks of ``count``, are computed and added,
  beside the shared experts, whole. The partial sum goes on to the next layer:
  what one chip of eight computes, with no stand-in for the others. ``None``:
  every expert (the stacks then hold all of them).
- *Rotary pairs.* The program rotates pairs ``(j, j + d/2)`` of a head; that
  is this file's map under a fixed permutation of each head's columns of
  ``W_q`` and ``W_k`` (scores are dot products, which a permutation applied to
  both sides leaves alone). A caller that hands over the program's parameters
  permutes those two matrices of the ``sliding_attention`` layers first
  (:func:`interleaved_columns`).
- *Shared experts averaged* is read as ``1 / num_shared_experts`` times their
  sum; the program stores them as one fused SwiGLU ``num_shared_experts`` times
  as wide (``ws_gate``, ``ws_up``, ``ws_down``), which is their sum.

The copy under ``tests/`` is byte for byte this file (a test holds them equal).
"""

import jax
import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def layer_norm(x, weight, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def interleaved_columns(w, head_dim):
    """The program's ``W_q`` or ``W_k`` [H, heads * d] with each head's columns
    reordered from its pairs ``(j, j + d/2)`` to the published ``(2j, 2j + 1)``:
    column ``2j`` of a head is the program's ``j``, column ``2j + 1`` its
    ``j + d/2``."""
    H, d = w.shape[0], head_dim
    halves = w.reshape(H, -1, 2, d // 2)  # [H, heads, first | second half, j]
    return jnp.swapaxes(halves, 2, 3).reshape(w.shape)


def rope(x, positions, theta):
    """x [S, heads, d] rotated by position, interleaved pairs (2j, 2j + 1)."""
    d = x.shape[-1]
    inv = jnp.asarray([1.0 / theta ** (2 * j / d) for j in range(d // 2)], jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * inv  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def attention(cfg, p, n, positions, kind, block=1024):
    """n [S, H] -> [S, H]: GQA over one sequence, causal, inside the window
    on a ``sliding_attention`` layer. Scores are made a block of queries and
    one kv head's group of query heads at a time."""
    S = n.shape[0]
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (n @ f32(p["wq"])).reshape(S, nh, d)
    k = (n @ f32(p["wk"])).reshape(S, nkv, d)
    v = (n @ f32(p["wv"])).reshape(S, nkv, d)
    windowed = kind == "sliding_attention"
    if windowed:
        q, k = rope(q, positions, cfg["rope_theta"]), rope(k, positions, cfg["rope_theta"])
    g = nh // nkv
    out = []
    for start in range(0, S, block):
        rows = positions[start:start + block, None]
        seen = positions[None, :] <= rows
        if windowed:
            seen = seen & (positions[None, :] > rows - cfg["sliding_window"])
        heads = []
        for h in range(nkv):
            scores = jnp.einsum("qgd,kd->gqk", q[start:start + block, h * g:(h + 1) * g],
                                k[:, h]) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            heads.append(jnp.einsum("gqk,kd->qgd", probs, v[:, h]))
        out.append(jnp.concatenate(heads, axis=1))  # [block, nh, d]
    return jnp.concatenate(out, axis=0).reshape(S, nh * d) @ f32(p["wo"])


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def route(cfg, p, h, given=None):
    """-> (chosen [S, k] expert ids, weights [S, k], slack [S]): sigmoid
    scores (``expert_selection_fn``), top-k of the scores (no bias term; ties
    to the lower id), weights from the scores, normalised over the k chosen
    (``norm_topk_prob``), no scaling factor. ``given`` [S, k] takes another's
    choice in place of the top-k (a discrete choice turns on the last bit of a
    score, so a comparison in lower precision conditions on it; a row of -1
    keeps this router's own); ``slack`` then says how far that choice lies
    under this router's own: the k-th best score minus the worst given one, 0
    for the same set."""
    k = cfg["num_experts_per_tok"]
    g = jax.nn.sigmoid(h @ f32(p["w_router"]))
    own = jnp.argsort(-g, axis=-1, stable=True)[:, :k]
    chosen = own if given is None else jnp.where(jnp.asarray(given)[:, :1] >= 0, given, own)
    slack = (jnp.min(jnp.take_along_axis(g, own, axis=-1), axis=-1)
             - jnp.min(jnp.take_along_axis(g, chosen, axis=-1), axis=-1))
    w = jnp.take_along_axis(g, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w, slack


def experts(cfg, p, h, experts_held=None, given=None, slacks=None):
    """The shared experts averaged, on every token, plus the routed experts
    by a loop over the held ones: stack row ``j`` is expert ``offset + j``."""
    chosen, w, slack = route(cfg, p, h, given)
    if slacks is not None:
        slacks.append(slack)
    offset, count = experts_held or (0, p["w_up"].shape[0])
    out = swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"]) / cfg["num_shared_experts"]
    for j in range(count):
        w_e = jnp.sum(jnp.where(chosen == offset + j, w, 0.0), axis=-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    return out


def layer(cfg, p, x, positions, kind, experts_held=None, given=None, slacks=None):
    """One parallel block on x [S, H]: both branches read the same norm."""
    n = layer_norm(x, p["norm"], cfg["layer_norm_eps"])
    return x + attention(cfg, p, n, positions, kind) + experts(
        cfg, p, n, experts_held, given, slacks)


def forward(cfg, params, tokens, experts_held=None, layer_fn=layer, positions=None,
            given=None, slacks=None):
    """One token sequence [S] -> logits [S, V] (``positions`` keeps those
    rows: at full width all of 7k rows are 0.9 GB). ``layer_fn`` lets a caller
    wrap :func:`layer` without changing what is computed; ``given`` (a list,
    one [S, k] a layer) and ``slacks`` (a list that gets one array a layer) are
    :func:`route`'s."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        x, pos = f32(jnp.take(params["embed"], tokens, axis=0)), jnp.arange(tokens.shape[0])
        for i in range(cfg["num_hidden_layers"]):
            routed = {"experts_held": experts_held, "slacks": slacks}
            if given is not None:
                routed["given"] = given[i]
            x = layer_fn(cfg, dict(params["layers"][i]), x, pos, cfg["layer_types"][i], **routed)
        h = layer_norm(x, params["final_norm"], cfg["layer_norm_eps"])
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return h @ f32(params["embed"]).T * cfg["logit_scale"]
