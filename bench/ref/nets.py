"""Plain actor-critic of AutoMDT §IV-D, its PPO loss and its AdamW step.

Written from the paper's description and the configuration's sizes, with
no code of the program under test: the policy is input -> Linear(256) ->
tanh -> 3 residual blocks (Linear, LayerNorm, ReLU, Linear, LayerNorm,
ReLU, plus skip) -> tanh -> Linear(3) scaled by ``action_scale``, with a
trainable log-std; the value is input -> Linear(256) -> tanh -> 2 residual
blocks with tanh -> Linear(1). Weights are drawn from the seed with the
same key schedule and initializers the trainer documents (truncated
normal at 1/sqrt(fan-in), zero biases, unit LayerNorm scales), so the
reference starts from the same point without taking the program's arrays.

``dtype`` picks the arithmetic of the matrix products: ``F32_DEFAULT``
runs them in float32 at the backend's default precision, which is the
precision the configuration states (on a TPU one bfloat16 pass with
float32 accumulation); float32 runs them at ``Precision.HIGHEST``;
``F8`` rounds each product's operands to float8 and is the control that a
sound comparison has to reject.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# float32 with matrix products at the backend's default precision
F32_DEFAULT = "float32_default"
# float32 arithmetic on matrix products whose operands are rounded to
# float8 (e4m3): the control one step below the bfloat16 operands that
# float32 products at the TPU's default precision use
F8 = "float8"
LOG_STD_MIN, LOG_STD_MAX = -2.0, 3.0


def _linear_init(key, d_in, d_out, stddev=None):
    stddev = stddev if stddev is not None else 1.0 / math.sqrt(d_in)
    w = stddev * jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out),
                                             F32)
    return {"w": w, "b": jnp.zeros((d_out,), F32)}


def _ln_init(d):
    return {"scale": jnp.ones((d,), F32), "bias": jnp.zeros((d,), F32)}


def _block_init(key, d):
    k1, k2 = jax.random.split(key)
    return {"l1": _linear_init(k1, d, d), "ln1": _ln_init(d),
            "l2": _linear_init(k2, d, d), "ln2": _ln_init(d)}


def policy_init(key, obs_dim, hidden, act_dim, action_scale, init_log_std):
    ks = jax.random.split(key, 6)
    return {
        "embed": _linear_init(ks[0], obs_dim, hidden),
        "b0": _block_init(ks[1], hidden),
        "b1": _block_init(ks[2], hidden),
        "b2": _block_init(ks[3], hidden),
        "mean": _linear_init(ks[4], hidden, act_dim, stddev=0.01),
        "mean_bias_units": jnp.ones((act_dim,), F32),
        "log_std": jnp.full((act_dim,), init_log_std, F32),
        "action_scale": jnp.asarray(action_scale, F32),
    }


def value_init(key, obs_dim, hidden):
    ks = jax.random.split(key, 4)
    return {"embed": _linear_init(ks[0], obs_dim, hidden),
            "b0": _block_init(ks[1], hidden),
            "b1": _block_init(ks[2], hidden),
            "out": _linear_init(ks[3], hidden, 1)}


def init_agent(seed, agent, obs_dim):
    """Initial (params, adam state) of a run seeded ``seed``: the run key
    is split into (init, rounds); init splits into (policy, value)."""
    key = jax.random.PRNGKey(seed)
    k_init, _ = jax.random.split(key)
    kp, kv = jax.random.split(k_init)
    params = {
        "policy": policy_init(kp, obs_dim, agent["hidden"], 3,
                              agent["action_scale"], agent["init_log_std"]),
        "value": value_init(kv, obs_dim, agent["hidden"]),
    }
    zeros = jax.tree.map(jnp.zeros_like, params)
    return params, {"m": zeros, "v": zeros, "step": jnp.zeros((), jnp.int32)}


def round_keys(seed, n):
    """The per-round episode keys of a run seeded ``seed``."""
    key = jax.random.PRNGKey(seed)
    _, key = jax.random.split(key)
    out = []
    for _ in range(n):
        key, k = jax.random.split(key)
        out.append(k)
    return out


def _dense(p, x, dtype):
    if dtype == F32:
        return jnp.dot(x, p["w"], precision=HIGHEST) + p["b"]
    if dtype == F8:
        def q(a):
            return a.astype(jnp.float8_e4m3fn).astype(F32)
        return jnp.dot(q(x), q(p["w"]), precision=HIGHEST) + p["b"]
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _compute(dtype):
    return F32 if dtype in (F32_DEFAULT, F8) else dtype


def _layernorm(p, x, dtype):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + 1e-5)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _block(p, x, act, dtype):
    h = act(_layernorm(p["ln1"], _dense(p["l1"], x, dtype), dtype))
    h = act(_layernorm(p["ln2"], _dense(p["l2"], h, dtype), dtype))
    return x + h


def policy_mean_std(p, obs, dtype=F32):
    """obs (..., D) -> (mean, std), thread units, returned in float32."""
    x = obs.astype(_compute(dtype))
    h = jnp.tanh(_dense(p["embed"], x, dtype))
    for b in ("b0", "b1", "b2"):
        h = _block(p[b], h, jax.nn.relu, dtype)
    raw = _dense(p["mean"], jnp.tanh(h), dtype) + p["mean_bias_units"].astype(
        x.dtype)
    mean = (raw * p["action_scale"].astype(x.dtype)).astype(F32)
    log_std = jnp.clip(p["log_std"], LOG_STD_MIN, LOG_STD_MAX)
    return mean, jnp.exp(log_std) * jnp.ones_like(mean)


def value(p, obs, dtype=F32):
    x = obs.astype(_compute(dtype))
    h = jnp.tanh(_dense(p["embed"], x, dtype))
    for b in ("b0", "b1"):
        h = _block(p[b], h, jnp.tanh, dtype)
    return _dense(p["out"], h, dtype)[..., 0].astype(F32)


def gaussian_logp(mean, std, a):
    return jnp.sum(-0.5 * (a - mean) ** 2 / std ** 2 - jnp.log(std)
                   - 0.5 * jnp.log(2 * jnp.pi), axis=-1)


def gaussian_entropy(std):
    return jnp.sum(0.5 * jnp.log(2 * jnp.pi * jnp.e) + jnp.log(std), axis=-1)


def _chunks(n, target=65536):
    """Fewest equal chunks of at most ``target`` rows that divide ``n``."""
    for k in range(max(1, -(-n // target)), n + 1):
        if n % k == 0:
            return k
    return n


def ppo_epoch_grads(params, batch, agent, dtype=F32, keep=None):
    """(loss, grads) of the clipped PPO objective over the whole batch,
    computed in blocks of at most 65,536 rows.

    loss = -mean(min(r A, clip(r) A)) + c_v mean((R - V)^2) - c_e mean(H),
    with A = R - stop_grad(V) normalized over the batch. ``keep`` (a row
    mask) is for planting the half-batch fault: the means run over the
    kept rows only."""
    obs, act, ret, logp_old = batch
    n = ret.shape[0]
    w = jnp.ones((n,), F32) if keep is None else keep.astype(F32)
    n_eff = w.sum()
    k = _chunks(n)

    def blocks(x):
        return x.reshape((k, n // k) + x.shape[1:])

    v_all = jax.lax.map(lambda o: value(params["value"], o, dtype),
                        blocks(obs)).reshape(n)
    adv = ret - v_all
    mu = (adv * w).sum() / n_eff
    sd = jnp.sqrt((((adv - mu) ** 2) * w).sum() / n_eff)

    def chunk_loss(p, xs):
        o, a, r, lo, wc = xs
        mean, std = policy_mean_std(p["policy"], o, dtype)
        logp = gaussian_logp(mean, std, a)
        v = value(p["value"], o, dtype)
        adv_c = (r - jax.lax.stop_gradient(v) - mu) / (sd + 1e-8)
        ratio = jnp.exp(logp - lo)
        surr = jnp.minimum(ratio * adv_c,
                           jnp.clip(ratio, 1 - agent["clip_eps"],
                                    1 + agent["clip_eps"]) * adv_c)
        total = (-(surr * wc).sum()
                 + agent["critic_coef"] * (((r - v) ** 2) * wc).sum()
                 - agent["entropy_coef"] * (gaussian_entropy(std) * wc).sum())
        return total / n_eff

    def step(carry, xs):
        l, g = jax.value_and_grad(chunk_loss)(params, xs)
        lc, gc = carry
        return (lc + l, jax.tree.map(jnp.add, gc, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(
        step, (jnp.zeros((), F32), zero),
        (blocks(obs), blocks(act), blocks(ret), blocks(logp_old), blocks(w)))
    return loss, grads


def adamw_step(params, grads, opt, agent, b1=0.9, b2=0.95, eps=1e-8):
    """Global-norm clipping to ``max_grad_norm``, then Adam (no decay)."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, agent["max_grad_norm"] / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = opt["step"] + 1
    tf = t.astype(F32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    params = jax.tree.map(
        lambda p, m, v: p - agent["lr"] * ((m / (1 - b1 ** tf))
                                           / (jnp.sqrt(v / (1 - b2 ** tf))
                                              + eps)),
        params, m, v)
    return params, {"m": m, "v": v, "step": t}


def discounted_returns(rew, gamma):
    """rew (M,) -> discounted returns (M,), accumulated from the end."""
    def back(g, r):
        g = r + gamma * g
        return g, g
    _, out = jax.lax.scan(back, jnp.zeros((), F32), rew, reverse=True)
    return out
