"""Plain reference of the Xing4.0 forward pass: jax.numpy, float32, no cache.

One file, no import from ``k_llms_tpu``: latent attention (MLA) with YaRN,
the sigmoid ``noaux_tc`` router over routed experts plus one shared expert,
and manifold-constrained hyper-connections (n residual streams mixed by a
Sinkhorn-normalised matrix), written straight from the equations in
``benchmark/configs/xing4-29b-a4b.json``'s ``source`` and ``assumed``. The
configuration is the published ``config.json`` as a dict (its own key names);
the parameters are the program's tree (``dense_layers`` and ``layers`` stacked
on a leading layer axis), upcast to float32 one use at a time so that the
full-width cut fits one chip: no float32 copy of an expert stack ever exists.

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise computed in bfloat16 passes.

The copy under ``tests/`` is byte for byte this file (a test holds them equal).
"""

import math

import jax
import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if weight is None else y * f32(weight)


# -- YaRN rotary embedding (DeepSeek-V3 conventions) -----------------------------

def yarn_mscale(factor, a):
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """Per-pair inverse frequencies of the rope dims under the config's YaRN."""
    d, base, sc = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    extra = [1.0 / base ** (2 * i / d) for i in range(d // 2)]
    if not sc:
        return jnp.asarray(extra, jnp.float32)

    def correction_dim(rotations):
        return (d * math.log(sc["original_max_position_embeddings"] / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), d - 1)
    span = max(high - low, 1e-3)
    out = []
    for i, e in enumerate(extra):
        ramp = min(max((i - low) / span, 0.0), 1.0)
        m = 1.0 - ramp  # 1: keep the frequency; 0: divide it by the factor
        out.append(e * m + (e / sc["factor"]) * (1.0 - m))
    return jnp.asarray(out, jnp.float32)


def attention_scale(cfg):
    sc = cfg["rope_scaling"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if sc and sc.get("mscale_all_dim"):
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rope(x, positions, inv_freq):
    """x [S, heads, d] rotated by position; pairs are (i, i + d/2), as the
    program's ``rope_embed`` has them. cos/sin carry mscale/mscale_all_dim = 1."""
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# -- the three mechanisms ------------------------------------------------------------

def mla(cfg, p, h):
    """Latent attention over one sequence, h [S, H] -> [S, H]; full causal,
    keys and values materialised per head from the latent."""
    S = h.shape[0]
    nh, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos, inv = jnp.arange(S), yarn_inv_freq(cfg)
    c_q = rms_norm(h @ f32(p["wq_a"]), p["q_norm"], eps)
    q = (c_q @ f32(p["wq_b"])).reshape(S, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, inv)
    kva = h @ f32(p["wkv_a"])
    c_kv = rms_norm(kva[:, :rkv], p["kv_norm"], eps)
    k_r = rope(kva[:, None, rkv:], pos, inv)[:, 0]  # one rope key for all heads
    kv = (c_kv @ f32(p["wkv_b"])).reshape(S, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * attention_scale(cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, nh * dv)
    return out @ f32(p["wo"])


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ f32(gate)) * (h @ f32(up))) @ f32(down)


def route(cfg, p, h, given=None):
    """-> (chosen [S, k] expert ids, weights [S, k], slack [S]): sigmoid
    scores, top-k of score + bias (ties to the lower id), weights from the
    scores alone. ``given`` [S, k] takes another's choice in place of the
    top-k (a discrete choice turns on the last bit of a score, so a comparison
    in lower precision conditions on it); ``slack`` then says how far that
    choice lies under this router's own: the k-th best score + bias minus the
    worst given one, 0 for the same set."""
    k = cfg["num_experts_per_tok"]
    g = jax.nn.sigmoid(h @ f32(p["w_router"]))
    ranked = g + f32(p["router_bias"])
    own = jnp.argsort(-ranked, axis=-1, stable=True)[:, :k]
    chosen = own if given is None else jnp.asarray(given)
    slack = (jnp.min(jnp.take_along_axis(ranked, own, axis=-1), axis=-1)
             - jnp.min(jnp.take_along_axis(ranked, chosen, axis=-1), axis=-1))
    w = jnp.take_along_axis(g, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"], slack


def experts(cfg, p, h, given=None, slacks=None):
    """Routed experts by a loop over the experts the tokens chose, plus the
    shared expert on every token."""
    chosen, w, slack = route(cfg, p, h, given)
    if slacks is not None:
        slacks.append(slack)
    out = swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    for e in range(cfg["n_routed_experts"]):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # 0 where e was not chosen
        out = out + w_e[:, None] * swiglu(h, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return out


def sinkhorn(m, iters, eps):
    """Rows, then columns, divided by their sums (+ eps), ``iters`` rounds."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper_connect(cfg, p, prefix, norm_weight, X, fn):
    """One sublayer under hyper-connections: X [S, n, H] ->
    H_res X + H_post^T fn(RMSNorm(H_pre X))."""
    S, n, H = X.shape
    x = rms_norm(X.reshape(S, n * H), None, cfg["rms_norm_eps"])
    proj = x @ f32(p[prefix + "_phi"])  # [S, n + n + n*n]
    alpha, bias = f32(p[prefix + "_alpha"]), f32(p[prefix + "_bias"])
    h_pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n: 2 * n] + bias[n: 2 * n])
    res = (alpha[2] * proj[:, 2 * n:] + bias[2 * n:]).reshape(S, n, n)
    res = jnp.clip(res, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    h_res = sinkhorn(jnp.exp(res), cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    y = fn(rms_norm(jnp.einsum("sn,snh->sh", h_pre, X), norm_weight, cfg["rms_norm_eps"]))
    return jnp.einsum("sij,sjh->sih", h_res, X) + h_post[:, :, None] * y[:, None, :]


# -- the forward pass ---------------------------------------------------------------------

def layer_params(params, i, first_k_dense):
    """Layer ``i``'s own arrays out of the stacked tree (still in their dtype)."""
    group, j = ("dense_layers", i) if i < first_k_dense else ("layers", i - first_k_dense)
    return {name: a[j] for name, a in params[group].items()}


def layer(cfg, p, X, given=None, slacks=None):
    """One layer on the streams X [S, n, H]; dense or routed by what ``p`` holds."""
    X = hyper_connect(cfg, p, "hc_attn", p["attn_norm"], X, lambda h: mla(cfg, p, h))
    if "w_router" in p:
        return hyper_connect(cfg, p, "hc_mlp", p["mlp_norm"], X,
                             lambda h: experts(cfg, p, h, given, slacks))
    return hyper_connect(cfg, p, "hc_mlp", p["mlp_norm"], X,
                         lambda h: swiglu(h, p["w_gate"], p["w_up"], p["w_down"]))


def forward(cfg, params, tokens, layer_fn=layer, positions=None, given=None, slacks=None):
    """Logits [S, V] of one token sequence [S]: the embedding copied into the
    n streams, every layer in turn, the streams summed, the final norm, the
    head. ``positions`` keeps the head to those rows of the sequence (at full
    width all S rows of logits are 0.8 GB); ``layer_fn`` lets a caller wrap
    :func:`layer` without changing what is computed; ``given`` [expert layers,
    S, k] and ``slacks`` (a list, one [S] a routed layer) are :func:`route`'s."""
    with jax.default_matmul_precision("highest"):
        x = f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        X = jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)
        dense = cfg["first_k_dense_replace"]
        for i in range(cfg["num_hidden_layers"]):
            routed = {} if given is None or i < dense else {"given": given[i - dense]}
            if slacks is not None and i >= dense:
                routed["slacks"] = slacks
            X = layer_fn(cfg, layer_params(params, i, dense), X, **routed)
        h = rms_norm(jnp.sum(X, axis=1), params["final_norm"], cfg["rms_norm_eps"])
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return h @ f32(params["lm_head"])
