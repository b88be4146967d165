"""Plain reference of the Nemotron-H forward pass: jax.numpy, float32, no cache.

One file, no import from ``k_llms_tpu``: a stack of single-mixer pre-norm
blocks chosen by ``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` routed
experts with one shared expert, ``*`` GQA attention), written straight from
the equations in ``benchmark/configs/nemotron3-nano-30b-a3b.json``'s ``source``
and ``assumed``. The configuration is the published ``config.json`` as a dict
(its own key names); the parameters are the program's tree (``layers`` a list
of per-layer dicts), upcast to float32 one use at a time so that the
full-width cut fits one chip: no float32 copy of an expert stack ever exists.

The state-space layer is the **sequential recurrence**, one token after the
other (``lax.scan`` over positions): no chunking, no cache, no kernels, no
batching. Every expert is a plain loop.

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise computed in bfloat16 passes.

Departures from the published model code (``modeling_nemotron_h.py``), each
without effect on the numbers: no ``time_step_limit`` clamp (it is (0, inf));
no rotary embedding anywhere (the attention there takes no positions and the
config's ``rope_theta`` is unused); ``residual_in_fp32`` is moot in float32;
the conv is written as K shifted multiply-adds in place of a grouped conv1d.
One departure is the program's: it stores each expert's ``W_up`` with zero
columns appended to a multiple of 128 (the chip's lane width), and the
reference reads the first ``moe_intermediate_size`` columns.

The copy under ``tests/`` is byte for byte this file (a test holds them equal).
"""

import jax
import jax.numpy as jnp


def f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# -- the three mixers ---------------------------------------------------------------

def mamba2(cfg, p, h):
    """Mamba-2 over one sequence, h [S, H] -> [S, H], from a zero state."""
    S = h.shape[0]
    nh, hd, G, N, K = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                       cfg["ssm_state_size"], cfg["conv_kernel"])
    d = nh * hd
    proj = h @ f32(p["in_proj"])  # [z | xBC | dt]
    z, xBC, dt = proj[:, :d], proj[:, d:d + d + 2 * G * N], proj[:, d + d + 2 * G * N:]
    # Causal depthwise conv: out[t] = sum_k w[k] * in[t - (K - 1) + k], zeros before the start.
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), jnp.float32), xBC])
    w = f32(p["conv_w"])  # [K, channels]
    conv = sum(padded[k:k + S] * w[k] for k in range(K)) + f32(p["conv_b"])
    xBC = jax.nn.silu(conv)
    x = xBC[:, :d].reshape(S, nh, hd)
    B = jnp.repeat(xBC[:, d:d + G * N].reshape(S, G, N), nh // G, axis=1)  # a group serves nh/G heads
    C = jnp.repeat(xBC[:, d + G * N:].reshape(S, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))  # [S, nh]
    A = -jnp.exp(f32(p["A_log"]))  # [nh]

    def step(state, t):  # state [nh, hd, N]
        x_t, B_t, C_t, dt_t = t
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, N), jnp.float32), (x, B, C, dt))
    y = (y + f32(p["D"])[:, None] * x).reshape(S, d)
    # Gate first, then an RMSNorm over each of the G groups of d / G channels.
    y = (y * jax.nn.silu(z)).reshape(S, G, d // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
    return (y.reshape(S, d) * f32(p["gate_norm"])) @ f32(p["out_proj"])


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
    """Routed non-gated experts ``W_down relu(W_up h)^2`` by a loop over the
    experts, plus the shared expert of the same form on every token."""
    chosen, w, slack = route(cfg, p, h, given)
    if slacks is not None:
        slacks.append(slack)
    out = relu2(h @ f32(p["ws_up"])) @ f32(p["ws_down"])
    for e in range(cfg["n_routed_experts"]):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # 0 where e was not chosen
        up = (h @ f32(p["w_up"][e]))[:, :cfg["moe_intermediate_size"]]
        out = out + w_e[:, None] * (relu2(up) @ f32(p["w_down"][e]))
    return out


def attention(cfg, p, h):
    """Causal GQA over one sequence, no rotary embedding, no bias."""
    S = h.shape[0]
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (h @ f32(p["wq"])).reshape(S, nkv, nq // nkv, hd)
    k = (h @ f32(p["wk"])).reshape(S, nkv, hd)
    v = (h @ f32(p["wv"])).reshape(S, nkv, hd)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(S, nq * hd) @ f32(p["wo"])


# -- the forward pass ---------------------------------------------------------------------

def layer(cfg, kind, p, x, given=None, slacks=None):
    """One block on x [S, H]: x + mixer(RMSNorm(x)), the mixer by ``kind``."""
    eps = cfg["layer_norm_epsilon"]
    if kind == "M":
        return x + mamba2(cfg, p, rms_norm(x, p["norm"], eps))
    if kind == "E":
        return x + experts(cfg, p, rms_norm(x, p["norm"], eps), given, slacks)
    if kind == "*":
        return x + attention(cfg, p, rms_norm(x, p["attn_norm"], eps))
    raise ValueError(f"layer kind {kind!r}")


def forward(cfg, params, tokens, layer_fn=layer, positions=None, given=None, slacks=None):
    """Logits [S, V] of one token sequence [S]: the embedding, every block in
    the pattern's order, the final norm, the head. ``positions`` keeps the head
    to those rows of the sequence (at full width all S rows of logits are
    0.3 GB); ``layer_fn`` lets a caller wrap :func:`layer` without changing
    what is computed; ``given`` [expert layers, S, k] and ``slacks`` (a list,
    one [S] a routed layer) are :func:`route`'s."""
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"] == len(params["layers"])
    with jax.default_matmul_precision("highest"):
        x = f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        routed_layers = 0
        for kind, p in zip(pattern, params["layers"]):
            routed = {}
            if kind == "E":
                if given is not None:
                    routed["given"] = given[routed_layers]
                if slacks is not None:
                    routed["slacks"] = slacks
                routed_layers += 1
            x = layer_fn(cfg, kind, dict(p), x, **routed)
        h = rms_norm(x, params["final_norm"], cfg["layer_norm_epsilon"])
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return h @ f32(params["lm_head"])
