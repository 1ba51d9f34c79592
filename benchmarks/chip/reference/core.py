"""The plain reference's shared pieces: precision-controlled matmuls, RMS
norm, the loss sums, AdamW, and the three-step training reference.

Nothing here imports the program.  Weights come from ``weights.py`` (made
from the seed by the benchmark), batches from ``traffic.py``.  ``fp32``
is the reference: float32 at the highest matmul precision.  ``fp8`` is the
control: the same arithmetic with every matmul operand rounded to float8
(e4m3 forward, e5m2 for the cotangents of the backward pass), each tensor
scaled by its largest magnitude first, as fp8 training does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def q8(x):
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _q8_fwd(x):
    return q8(x), None


def _q8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, E5M2_MAX),)


q8.defvjp(_q8_fwd, _q8_bwd)


def mm(spec: str, a, b, precision: str):
    """einsum in float32; under ``fp8`` both operands are fp8-rounded."""
    if precision == "fp8":
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def ce_sums(logits, labels):
    """(sum over tokens of lse - gold, sum of lse^2)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold), jnp.sum(lse * lse)


def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> dict:
    """{leaf name: float32 norm} of a pytree, as one traced dict."""
    return dict(zip(leaf_names(tree),
                    (jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                     for x in jax.tree.leaves(tree))))


def adamw(master, grads, m, v, count, opt):
    """The update the job states (decoupled weight decay, global-norm clip,
    linear warm-up, bias correction); returns (master, m, v, count,
    clipped grads)."""
    count = count + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    lr = opt["lr"] * jnp.minimum(count / max(opt["warmup_steps"], 1), 1.0)
    c = count.astype(F32)
    bc1, bc2 = 1 - opt["b1"] ** c, 1 - opt["b2"] ** c
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, x: opt["b1"] * a + (1 - opt["b1"]) * x, m, g)
    v = jax.tree.map(lambda a, x: opt["b2"] * a + (1 - opt["b2"]) * x * x,
                     v, g)
    master = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + opt["eps"])
                                  + opt["weight_decay"] * p), master, m, v)
    return master, m, v, count, g


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def stored(x, dtype):
    """x as the program holds it: rounded to its stored type; the gradient
    passes through to the float32 master unrounded.  ``reduce_precision``
    rounds where a cast there and back would not: the TPU compiler drops
    such a pair of casts and keeps the float32 value (excess precision)."""
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


stored.defvjp(lambda x, dtype: (stored(x, dtype), None),
              lambda dtype, _, g: (g,))


def make_grad(model, cfg, dtypes, z_loss, precision, fault=None,
              batch_sharding=None):
    """One jitted step's loss and gradient over the whole batch, in float32
    from the master rounded to each leaf's stored type (the configuration
    stores bf16 weights beside an fp32 master); the head and the loss run
    one row at a time.

    ``fault`` plants one of the faults the check must catch, in the
    reference put in the program's place: ``half_batch`` (loss and update
    over the first half of the rows), ``no_exchange`` (the update from the
    first half of the rows, as a replica that never receives the others'
    gradients; the loss over all)."""

    @jax.checkpoint
    def head_terms(w, x, lab):
        return ce_sums(mm("sd,dv->sv", x, w, precision), lab)

    def grad(master, tokens, labels):
        B, S = tokens.shape
        if batch_sharding is not None:
            tokens = jax.lax.with_sharding_constraint(tokens, batch_sharding)
            labels = jax.lax.with_sharding_constraint(labels, batch_sharding)
        row = jnp.arange(B)
        n_loss = B // 2 if fault == "half_batch" else B
        n_grad = B // 2 if fault in ("half_batch", "no_exchange") else B
        w_loss = (row < n_loss).astype(F32)
        w_grad = (row < n_grad).astype(F32)

        def objective(master):
            params = jax.tree.map(stored, master, dtypes)
            x = model.features(params, tokens, cfg, precision)
            ce, zz = jax.lax.map(
                lambda xs: head_terms(params["unembed"], *xs), (x, labels))
            return jnp.sum(w_grad * (ce + z_loss * zz)) / (n_grad * S), \
                (ce, zz)

        (_, (ce, zz)), grads = jax.value_and_grad(
            objective, has_aux=True)(master)
        return jnp.sum(w_loss * (ce + z_loss * zz)) / (n_loss * S), grads

    return grad


def train_readings(model, cfg, make_params0, dtypes, batches, opt, z_loss,
                   precision="fp32", fault=None, shardings=None,
                   batch_sharding=None):
    """Runs the reference (or, with ``precision``/``fault``, a control or a
    planted fault in the program's place) for ``len(batches)`` steps from
    the float32 weights ``make_params0()`` gives, and returns the readings
    the check compares:
    the loss of each step, each leaf's norm of the first step's gradient as
    the optimizer gets it (clipped), and each leaf's norm of the master's
    change over all steps."""
    # the gradient and the update are two programs, so that the step's
    # activations and the optimizer's state need not share the memory
    grad = jax.jit(make_grad(model, cfg, dtypes, z_loss, precision, fault,
                             batch_sharding),
                   out_shardings=(None, shardings))

    def update(state, grads):
        master, m, v, count = state
        master, m, v, count, g = adamw(master, grads, m, v, count, opt)
        return (master, m, v, count), leaf_norms(g)

    update = jax.jit(update, donate_argnums=0,
                     out_shardings=((shardings, shardings, shardings, None),
                                    None))
    master = make_params0()
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=shardings)
    state = (master, zeros(master), zeros(master), jnp.zeros((), jnp.int32))
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for tokens, labels in batches:
            loss, grads = grad(state[0], tokens, labels)
            state, gn = update(state, grads)
            del grads
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(x) for k, x in gn.items()}
        # the starting weights are made again, rather than kept, to leave
        # the device's memory to the steps
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(state[0], make_params0())
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in change.items()}}
