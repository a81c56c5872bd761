"""The reference's side of a training cell: weights from the seed, the
optimizers as published, and the first steps followed in float32.

Nothing here imports the program. `follow` is also the control: with
`precision` set below the configuration's own it is the reference put in the
program's place, computed one step down in precision.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec, key):
    out = []
    for k, (_, shape, init, _) in zip(jax.random.split(key, len(spec)), spec):
        if init == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif init == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(init[1] * jax.random.normal(k, shape, jnp.float32))
    return out


def make_weights(spec, seed):
    """Every leaf of `spec` in one jitted call on the default device, float32
    (the master type), brought to the host once: {name: numpy array}."""
    spec = tuple((n, tuple(s), i if isinstance(i, str) else tuple(i), t)
                 for n, s, i, t in spec)
    leaves = jax.device_get(_make(spec, seed_key(seed)))
    return {n: a for (n, _, _, _), a in zip(spec, leaves)}


# ---------------------------------------------------------------------------
# precision of the matrix products
# ---------------------------------------------------------------------------

def _scaled_round(a, dtype, top):
    # clipped: a quotient a hair over the format's largest number would
    # become NaN (e4m3fn has no infinity) or infinite
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    scaled = jnp.clip(a / scale, -top, top)
    return scaled.astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(a):
    return _scaled_round(a, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda a: (_fp8(a), None),
            lambda _, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


def quantizer(precision):
    """What rounds every tensor of the forward pass (the operands of the
    matrix products and convolutions, and each layer's stored output) and
    the gradient that flows back to it.

    float32: nothing. float8: per-tensor scaled FP8 as low-precision training
    recipes use it, the step below the bfloat16 that the configurations state
    and in which the program keeps those same tensors: scaled by the largest
    magnitude to the format's range, rounded to float8_e4m3fn forward and to
    float8_e5m2 backward."""
    if precision == "float32":
        return lambda a: a
    if precision == "float8":
        return _fp8
    raise ValueError(f"no quantizer for precision {precision!r}")


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _sgd(opt):
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    def init(w):
        return jnp.zeros_like(w)

    def update(w, g, s, t):
        mom = mu * s - lr * (g + wd * w)
        return w + mom, mom
    return init, update


def _adamw(opt):
    lr, wd = opt["learning_rate"], opt["wd"]
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]

    def init(w):
        return (jnp.zeros_like(w), jnp.zeros_like(w))

    def update(w, g, s, t):
        m = b1 * s[0] + (1 - b1) * g
        v = b2 * s[1] + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return w - (lr * mhat / (jnp.sqrt(vhat) + eps) + wd * w), (m, v)
    return init, update


OPTIMIZERS = {"sgd": _sgd, "adamw": _adamw}


def first_gradient(opt, state, w_after):
    """The gradient the optimizer got at step 1, from its state after that
    step (numpy, one leaf). sgd: mom1 = -lr (g + wd w0), w1 = w0 + mom1.
    adamw: m1 = (1 - beta1) g."""
    if opt["name"] == "sgd":
        mom = np.asarray(state, np.float32)
        w0 = np.asarray(w_after, np.float32) - mom
        return -mom / opt["learning_rate"] - opt["wd"] * w0
    if opt["name"] == "adamw":
        return np.asarray(state[0], np.float32) / (1 - opt["beta1"])
    raise ValueError(f"no first_gradient rule for {opt['name']!r}")


# ---------------------------------------------------------------------------
# the first steps, followed
# ---------------------------------------------------------------------------

def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


_STEPPERS = {}


def _stepper(model, cfg, precision, row_blocks):
    """The jitted training step of one (model, configuration, precision),
    built once in a process: (params, frozen, state, x, y, t) -> (params,
    state, loss, per-leaf gradient norms)."""
    key = (model.__name__, json.dumps(cfg, sort_keys=True), precision,
           row_blocks)
    if key in _STEPPERS:
        return _STEPPERS[key]
    if row_blocks != 1 and not model.ROW_BLOCKS_OK:
        raise ValueError(f"{model.__name__} couples the rows of a batch: "
                         f"it cannot be followed in {row_blocks} blocks")
    opt = cfg["optimizer"]
    init, update = OPTIMIZERS[opt["name"]](opt)
    q = quantizer(precision)

    def loss_of(ps, frozen, x, y):
        with jax.default_matmul_precision("highest"):
            return model.loss({**ps, **frozen}, x, y, cfg, q)

    @jax.jit
    def step(ps, frozen, st, x, y, t):
        if row_blocks == 1:
            lossv, grads = jax.value_and_grad(loss_of)(ps, frozen, x, y)
        else:
            xb = x.reshape((row_blocks, -1) + x.shape[1:])
            yb = y.reshape((row_blocks, -1) + y.shape[1:])

            def body(acc, xy):
                lv, g = jax.value_and_grad(loss_of)(ps, frozen, *xy)
                return (acc[0] + lv / row_blocks, jax.tree_util.tree_map(
                    lambda a, b: a + b / row_blocks, acc[1], g)), None
            zero = (jnp.float32(0), jax.tree_util.tree_map(
                jnp.zeros_like, ps))
            (lossv, grads), _ = jax.lax.scan(body, zero, (xb, yb))
        new = {n: update(ps[n], grads[n], st[n], t) for n in ps}
        return ({n: v[0] for n, v in new.items()},
                {n: v[1] for n, v in new.items()}, lossv,
                {n: _norm(grads[n]) for n in ps})

    _STEPPERS[key] = (init, step)
    return init, step


@jax.jit
def _change_norms(after, before):
    return {n: _norm(after[n] - before[n]) for n in after}


def follow(model, cfg, weights, batches, precision="float32", row_blocks=1):
    """Train `weights` over `batches` (host (x, y) pairs), one step each, as
    the configuration states but in float32 at the highest matmul precision
    (`precision` rounds the operands of the matrix products: the control).

    Returns {"losses": [..], "grad_norms": {leaf: norm of the first step's
    gradient}, "change_norms": {leaf: norm of the change over all steps}}
    for the trainable leaves."""
    init, step = _stepper(model, cfg, precision, row_blocks)
    spec = model.param_spec(cfg)
    frozen = {n: jnp.asarray(weights[n]) for n, _, _, t in spec if not t}
    params = {n: jnp.asarray(weights[n]) for n, _, _, t in spec if t}
    state = {n: init(w) for n, w in params.items()}
    losses, grad_norms = [], None
    for i, (x, y) in enumerate(batches):
        params, state, lossv, gn = step(params, frozen, state,
                                        jnp.asarray(x), jnp.asarray(y),
                                        jnp.float32(i + 1))
        losses.append(float(lossv))
        if i == 0:
            grad_norms = {n: float(v) for n, v in gn.items()}
    change = _change_norms(params, {n: jnp.asarray(weights[n])
                                    for n in params})
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {n: float(v) for n, v in change.items()}}
