"""Plain reference of the JoyAI-LLM-Flash forward pass: jax.numpy, float32, no cache.

One file, no import from ``k_llms_tpu``: latent attention (MLA) under plain
RoPE, pre-norm residual blocks, one dense SwiGLU layer, then the sigmoid
``noaux_tc`` router over routed SwiGLU experts plus one shared expert, and the
next-token module of DeepSeek-V3 (arXiv:2412.19437 section 2.2, depth 1) behind
the stack, written straight from the equations in
``benchmark/configs/joyai-llm-flash.json``'s ``source`` and ``assumed``. No
cache, no kernel, no batching: one sequence, keys and values materialised per
head, every chosen expert by a Python loop. The configuration is the published
``config.json`` as a dict (its own key names); the parameters are the
program's tree (``dense_layers``, ``layers`` and ``mtp.layers`` stacked on a
leading layer axis), upcast to float32 one use at a time so that the
full-width cut fits one chip.

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise computed in bfloat16 passes.

Departures from the published description, each also under ``assumed`` in the
configuration's file:

- *A share of the experts.* ``experts_held = (offset, count)``: the router is
  ``n_routed_experts`` wide (the published 256), chooses and normalises over
  all of them, and only the chosen experts in ``[offset, offset + count)``,
  whose weights ``params`` holds as stacks of ``count``, are computed and
  added, beside the shared expert, whole. The partial sum goes on to the next
  layer: what one chip of two computes, with no stand-in for the other chip.
  ``None``: every expert (the stacks then hold all of them).
- *Rotary pairs* are ``(i, i + d/2)``, as the program's ``rope_embed`` has
  them; the released code's ``rope_interleave`` de-interleaves to the same.
- *The module's wiring*, which a ``config.json`` does not fix: the
  concatenation is ``[RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]`` in that order
  (the paper's), ``h_i`` is the main stack's output BEFORE ``final_norm``, the
  block is one routed-expert layer like the stack's, its output goes through
  the module's own final RMSNorm and then the main model's head, and its
  rotary position is ``i + 1``, the position of the token it embeds (scores
  depend on position differences alone, so ``i`` would give the same logits).

The copy under ``tests/`` is byte for byte this file (a test holds them equal).
"""

import jax
import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def rope(x, positions, cfg):
    """x [S, heads, d] rotated by position: plain RoPE at ``rope_theta``
    (``rope_scaling`` is null), pairs (i, i + d/2)."""
    d = x.shape[-1]
    inv = jnp.asarray([1.0 / cfg["rope_theta"] ** (2 * i / d) for i in range(d // 2)], jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * inv  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def mla(cfg, p, h, positions):
    """Latent attention over one sequence, h [S, H] -> [S, H]; full causal,
    keys and values materialised per head from the latent."""
    S = h.shape[0]
    nh, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = rms_norm(h @ f32(p["wq_a"]), p["q_norm"], eps)
    q = (c_q @ f32(p["wq_b"])).reshape(S, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], positions, cfg)
    kva = h @ f32(p["wkv_a"])
    c_kv = rms_norm(kva[:, :rkv], p["kv_norm"], eps)
    k_r = rope(kva[:, None, rkv:], positions, cfg)[:, 0]  # one rope key for all heads
    kv = (c_kv @ f32(p["wkv_b"])).reshape(S, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * (dn + dr) ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, nh * dv)
    return out @ f32(p["wo"])


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def route(cfg, p, h, given=None):
    """-> (chosen [S, k] expert ids, weights [S, k], slack [S]): sigmoid
    scores, top-k of score + bias (ties to the lower id; ``n_group`` =
    ``topk_group`` = 1, so no group step), weights from the scores alone,
    normalised over all k chosen (``norm_topk_prob``), times
    ``routed_scaling_factor``. ``given`` [S, k] takes another's choice in place
    of the top-k (a discrete choice turns on the last bit of a score, so a
    comparison in lower precision conditions on it; a row of -1 keeps this
    router's own); ``slack`` then says how
    far that choice lies under this router's own: the k-th best score + bias
    minus the worst given one, 0 for the same set."""
    k = cfg["num_experts_per_tok"]
    g = jax.nn.sigmoid(h @ f32(p["w_router"]))
    ranked = g + f32(p["router_bias"])
    own = jnp.argsort(-ranked, axis=-1, stable=True)[:, :k]
    chosen = own if given is None else jnp.where(jnp.asarray(given)[:, :1] >= 0, given, own)
    slack = (jnp.min(jnp.take_along_axis(ranked, own, axis=-1), axis=-1)
             - jnp.min(jnp.take_along_axis(ranked, chosen, axis=-1), axis=-1))
    w = jnp.take_along_axis(g, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"], slack


def experts(cfg, p, h, experts_held=None, given=None, slacks=None):
    """The shared expert on every token, plus the routed experts by a loop
    over the held ones: stack row ``j`` is expert ``offset + j``."""
    chosen, w, slack = route(cfg, p, h, given)
    if slacks is not None:
        slacks.append(slack)
    offset, count = experts_held or (0, p["w_up"].shape[0])
    out = swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    for j in range(count):
        w_e = jnp.sum(jnp.where(chosen == offset + j, w, 0.0), axis=-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    return out


def layer(cfg, p, x, positions, experts_held=None, given=None, slacks=None):
    """One pre-norm block on x [S, H]; dense or routed by what ``p`` holds."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(cfg, p, rms_norm(x, p["attn_norm"], eps), positions)
    h = rms_norm(x, p["mlp_norm"], eps)
    if "w_router" in p:
        return x + experts(cfg, p, h, experts_held, given, slacks)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def layer_params(params, i, first_k_dense):
    """Layer ``i``'s own arrays out of the stacked tree (still in their dtype)."""
    group, j = ("dense_layers", i) if i < first_k_dense else ("layers", i - first_k_dense)
    return {name: a[j] for name, a in params[group].items()}


def forward(cfg, params, tokens, experts_held=None, layer_fn=layer, positions=None,
            given=None, slacks=None):
    """One token sequence [S] -> (logits [S, V], the module's logits [S - 1, V]:
    row ``i``, from the pair ``(h_i, t_{i+1})``, is for token ``i + 2``).
    ``positions`` keeps both heads to those rows (the module's to ``positions
    - 1``, dropping a -1: the row whose pair ends at that position; at full
    width all S rows of logits are 0.8 GB); ``layer_fn`` lets a caller wrap
    :func:`layer` without changing what is computed; ``given`` (a list, one [S,
    k] a routed layer of the stack, then the module's [S - 1, k]) and
    ``slacks`` (a list that gets one array a routed layer, the module's last)
    are :func:`route`'s."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        S, eps = tokens.shape[0], cfg["rms_norm_eps"]
        emb = f32(jnp.take(params["embed"], tokens, axis=0))
        x, dense, pos = emb, cfg["first_k_dense_replace"], jnp.arange(S)
        for i in range(cfg["num_hidden_layers"]):
            routed = {} if i < dense else {"experts_held": experts_held, "slacks": slacks}
            if given is not None and i >= dense:
                routed["given"] = given[i - dense]
            x = layer_fn(cfg, layer_params(params, i, dense), x, pos, **routed)
        keep = None if positions is None else jnp.asarray(positions)
        h = rms_norm(x, params["final_norm"], eps)
        logits = (h if keep is None else h[keep]) @ f32(params["lm_head"])
        if not cfg["num_nextn_predict_layers"]:
            return logits, None
        mtp = params["mtp"]
        pair = jnp.concatenate([rms_norm(x[:-1], mtp["hnorm"], eps),
                                rms_norm(emb[1:], mtp["enorm"], eps)], axis=-1)
        block = {name: a[0] for name, a in mtp["layers"].items()}
        routed = {"experts_held": experts_held, "slacks": slacks}
        if given is not None:
            routed["given"] = given[-1]
        y = layer_fn(cfg, block, pair @ f32(mtp["eh_proj"]), pos[1:], **routed)
        y = rms_norm(y, mtp["final_norm"], eps)
        if keep is not None:
            y = y[keep[keep >= 1] - 1]
        return logits, y @ f32(params["lm_head"])
