"""Plain float32 reference of the configuration's training steps.

A dense decoder as the configuration describes it (OLMo: non-parametric
LayerNorm, multi-head attention with split-half RoPE, SwiGLU MLP, tied
embeddings, next-token cross-entropy), trained by AdamW with global-norm
clipping and linear warmup into a cosine decay. Every matrix product runs at
``Precision.HIGHEST``. It imports nothing of the program under test. Its
initial weights come from the seed by the configuration's init rule (a
normal draw per parameter from a key folded with a hash of the parameter's
path, scaled by 1/sqrt(fan_in); the embedding by 0.02), so it starts where
the program starts without taking the program's arrays.

Memory: attention runs in blocks of queries, the loss in blocks of
positions, each layer under ``jax.checkpoint``. Given the cell's devices,
``train`` places its own params, moments and gradients over them by a plain
rule of its own (``placement``), and for a batch that does not fit at once
it sums the loss and gradient over blocks of rows.

``state_dtype``/``compute_dtype`` make the controls: the same steps with the
state kept in a lower precision, or with the products' inputs rounded to
one. ``half_batch`` makes a planted fault: the loss over the first half of
the rows only.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512
LOSS_BLOCK = 512


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    d_head: int
    d_ff: int
    vocab: int
    eps: float
    theta: float

    @classmethod
    def of(cls, model: dict) -> "Dims":
        want = {"norm": "nonparam_ln", "activation": "swiglu",
                "tie_embeddings": True, "use_bias": False}
        for k, v in want.items():
            if model.get(k, v) != v:
                raise ValueError(f"reference covers {k}={v!r}, config has "
                                 f"{model.get(k)!r}")
        if model["n_kv_heads"] != model["n_heads"]:
            raise ValueError("reference covers multi-head attention only")
        return cls(model["n_layers"], model["d_model"], model["n_heads"],
                   model["d_head"], model["d_ff"], model["vocab_size"],
                   model.get("norm_eps", 1e-5), model.get("rope_theta", 1e4))


# --------------------------------------------------------------------------- #
# Initial weights from the seed
# --------------------------------------------------------------------------- #
def _path_key(root, path: str):
    h = np.uint32(int.from_bytes(path.encode(), "little") % (2**31 - 1))
    return jax.random.fold_in(root, h)


def init_params(dims: Dims, seed: int) -> dict:
    """Weights at step 0, float32, in the program's tree layout."""
    pkey, _ = jax.random.split(jax.random.key(seed))

    def normal(path, shape, std):
        return jax.random.normal(_path_key(pkey, path), shape,
                                 jnp.float32) * np.float32(std)

    d, hd, f = dims.d, dims.heads * dims.d_head, dims.d_ff
    shapes = {"attn": {"wq": (d, hd), "wk": (d, hd), "wv": (d, hd),
                       "wo": (hd, d)},
              "mlp": {"wi": (d, f), "wg": (d, f), "wo": (f, d)}}
    stacks: Dict[str, Dict[str, list]] = {"attn": {}, "mlp": {}}
    for i in range(dims.layers):
        for blk, leaves in shapes.items():
            for name, shape in leaves.items():
                path = f"decoder/stack/layer{i}/l0/{blk}/{name}"
                stacks[blk].setdefault(name, []).append(
                    normal(path, shape, 1.0 / np.sqrt(shape[0])))
    # the norms have no parameters: empty nodes, as the program has them
    layer = {"mix": {k: jnp.stack(v) for k, v in stacks["attn"].items()},
             "mlp": {k: jnp.stack(v) for k, v in stacks["mlp"].items()},
             "norm1": {}, "norm2": {}}
    return {"norm_f": {}, "segments": {"stack": {"l0": layer}},
            "tok": {"table": normal("embed/table", (dims.vocab, dims.d),
                                    0.02)}}


# --------------------------------------------------------------------------- #
# Forward and loss
# --------------------------------------------------------------------------- #
def _mm(cdt):
    def mm(spec, a, b):
        if cdt == jnp.float32:
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        a, b = a.astype(cdt), b.astype(cdt)
        if cdt != jnp.bfloat16:                 # e.g. fp8: rounded inputs
            a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return mm


def _norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps)


def _rope(x, theta):
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, mm):
    """Causal softmax attention, one block of queries at a time."""
    b, s, h, dh = q.shape
    qb = min(Q_BLOCK, s)
    scale = np.float32(1.0 / np.sqrt(dh))

    @jax.checkpoint
    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = mm("bqhd,bkhd->bhqk", qi, k) * scale
        keep = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        w = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", w, v)

    out = lax.map(block, jnp.arange(s // qb))           # (n, b, qb, h, dh)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)


def loss(params, tokens, labels, dims: Dims, cdt=jnp.float32):
    """Mean next-token cross-entropy over every position of the batch."""
    b, s = tokens.shape
    return nll_sum(params, tokens, labels, dims, cdt) / (b * s)


def nll_sum(params, tokens, labels, dims: Dims, cdt=jnp.float32, keep=None):
    """Next-token cross-entropy summed over every position of the rows, each
    row's weighted by ``keep`` (one weight a row) where given."""
    mm = _mm(cdt)
    b, s = tokens.shape
    table = params["tok"]["table"].astype(jnp.float32)
    x = table[tokens]

    @jax.checkpoint
    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = _norm(x, dims.eps)
        q, k, v = (mm("bsd,de->bse", h, p["mix"][n]).reshape(
            b, s, dims.heads, dims.d_head) for n in ("wq", "wk", "wv"))
        o = _attention(_rope(q, dims.theta), _rope(k, dims.theta), v, mm)
        x = x + mm("bse,ed->bsd", o.reshape(b, s, -1), p["mix"]["wo"])
        h = _norm(x, dims.eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, p["mlp"]["wg"]))
        u = mm("bsd,df->bsf", h, p["mlp"]["wi"])
        return x + mm("bsf,fd->bsd", g * u, p["mlp"]["wo"]), None

    x, _ = lax.scan(layer, x, params["segments"]["stack"]["l0"])
    x = _norm(x, dims.eps)
    lb = min(LOSS_BLOCK, s)

    @jax.checkpoint
    def block_nll(i):
        xi = lax.dynamic_slice_in_dim(x, i * lb, lb, axis=1)
        yi = lax.dynamic_slice_in_dim(labels, i * lb, lb, axis=1)
        logits = mm("bsd,vd->bsv", xi, table)
        picked = jnp.take_along_axis(logits, yi[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(nll if keep is None else nll * keep[:, None])

    return jnp.sum(lax.map(block_nll, jnp.arange(s // lb)))


# --------------------------------------------------------------------------- #
# AdamW
# --------------------------------------------------------------------------- #
def lr_at(opt: dict, step: int) -> float:
    if step < opt["warmup_steps"]:
        return opt["lr"] * step / max(opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0), 1)
    return opt["lr"] * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"])
                        * 0.5 * (1 + np.cos(np.pi * prog)))


def adam(params, grads, m, v, lr, t, opt: dict):
    """One AdamW step at (python) step count ``t`` (1-based). Returns new
    params, moments, and the clipped gradient the update used."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                         jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    c1, c2 = 1.0 - opt["b1"] ** t, 1.0 - opt["b2"] ** t

    def one(p, g, m_, v_):
        dt = p.dtype
        p, g = p.astype(jnp.float32), g.astype(jnp.float32) * scale
        m_ = opt["b1"] * m_.astype(jnp.float32) + (1 - opt["b1"]) * g
        v_ = opt["b2"] * v_.astype(jnp.float32) + (1 - opt["b2"]) * g * g
        upd = (m_ / c1) / (jnp.sqrt(v_ / c2) + opt["eps"])
        if p.ndim >= 2:
            upd = upd + opt["weight_decay"] * p
        return (p - lr * upd).astype(dt), m_.astype(dt), v_.astype(dt), g

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,            # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)


# --------------------------------------------------------------------------- #
def leaf_name(keypath) -> str:
    """'segments/stack/l0/mix/wq' for a leaf's key path."""
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in keypath)


def slice_sq_norms(tree) -> Dict[str, jax.Array]:
    """Squared norm of each leaf, and of each layer of a stacked leaf (ndim
    >= 3: the leading axis is the layer), keyed '<path>' or '<path>[i]'."""
    out = {}
    for kp, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = leaf_name(kp)
        sq = jnp.square(x.astype(jnp.float32))
        if x.ndim >= 3:
            per = jnp.sum(sq.reshape(x.shape[0], -1), axis=1)
            for i in range(x.shape[0]):
                out[f"{name}[{i}]"] = per[i]
        else:
            out[name] = jnp.sum(sq)
    return out


def placement(shape, mesh: Mesh) -> NamedSharding:
    """The reference's own rule for where a leaf lives: split along its
    largest axis that the device count divides (the first of equals), else
    whole on every device."""
    n = mesh.devices.size
    axes = [i for i, k in enumerate(shape) if k % n == 0]
    if not axes:
        return NamedSharding(mesh, P())
    split = max(axes, key=lambda i: (shape[i], -i))
    return NamedSharding(mesh, P(*("r" if i == split else None
                                   for i in range(len(shape)))))


def train(model: dict, opt: dict, seed: int, batches: Sequence[dict], *,
          devices: Sequence = (), row_block: int = 0,
          state_dtype=jnp.float32, compute_dtype=jnp.float32,
          half_batch: bool = False) -> dict:
    """Run ``len(batches)`` steps from the seed's weights. Returns the loss
    of each step, the per-slice norms of the first clipped gradient, and of
    the weights' change over all the steps.

    With more than one of ``devices``, params, moments and gradients are
    placed over them by ``placement``, and each block's rows are split over
    them: a block that the device count does not divide is padded with rows
    of weight 0. ``row_block``: loss and gradient are summed in float32
    over blocks of that many rows, and divided once by the number of
    positions. With one device and no ``row_block`` the whole batch runs at
    once on the default device."""
    dims = Dims.of(model)
    rows = len(batches[0]["tokens"])
    if half_batch:      # the first half of the rows
        rows = rows // 2
    spread = len(devices) > 1
    if not (spread or row_block):
        return _train_whole(dims, opt, seed, batches, rows, state_dtype,
                            compute_dtype)

    f = programs(dims, opt, seed, devices if spread else None, state_dtype,
                 compute_dtype)
    block = row_block or rows
    n_dev = len(devices) if spread else 1
    params = f.init()
    m, v = f.moments(params), f.moments(params)
    losses: List[float] = []
    grad_sq = None
    for i, bt in enumerate(batches):
        g, total = f.zeros()
        for lo in range(0, rows, block):
            hi = min(lo + block, rows)
            pad = -(hi - lo) % n_dev
            t, y = (jax.device_put(np.pad(np.asarray(bt[k][lo:hi]),
                                          ((0, pad), (0, 0))), f.rows)
                    for k in ("tokens", "labels"))
            keep = None if not pad else jax.device_put(
                np.repeat(np.float32([1, 0]), [hi - lo, pad]), f.rows)
            g, total = f.accumulate(params, g, total, t, y, keep)
        g, total = f.mean(g, total, np.float32(rows * len(bt["tokens"][0])))
        losses.append(float(total))
        params, m, v, g = f.step(params, g, m, v, np.float32(lr_at(opt, i)),
                                 np.float32(i + 1))
        if grad_sq is None:
            grad_sq = {k: float(x) for k, x in f.sq(g).items()}
        del g
    del m, v
    ch = {k: float(x) for k, x in f.change(params, f.init()).items()}
    return _result(losses, grad_sq, ch)


def programs(dims: Dims, opt: dict, seed: int, devices, state_dtype=jnp.float32,
             compute_dtype=jnp.float32) -> SimpleNamespace:
    """The jitted parts of a run in blocks of rows: over ``devices`` by
    ``placement`` where given, else on the default device. ``rows`` is
    where a block's rows go: split over the devices."""
    shapes = jax.eval_shape(lambda: init_params(dims, seed))
    if devices:
        mesh = Mesh(np.array(list(devices)), ("r",),
                    axis_types=(AxisType.Auto,))
        psh = jax.tree.map(lambda x: placement(x.shape, mesh), shapes)
        one = NamedSharding(mesh, P())
        split = NamedSharding(mesh, P("r"))
        kw = lambda out: {"out_shardings": out}                # noqa: E731
    else:
        psh = one = split = None
        kw = lambda out: {}                                    # noqa: E731

    def add_block(p, g, total, t, y, keep):
        val, grad = jax.value_and_grad(
            lambda q: nll_sum(q, t, y, dims, compute_dtype, keep))(p)
        return jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g,
                            grad), total + val

    return SimpleNamespace(
        rows=split,
        init=jax.jit(lambda: jax.tree.map(lambda a: a.astype(state_dtype),
                                          init_params(dims, seed)),
                     **kw(psh)),
        zeros=jax.jit(lambda: (jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), shapes),
            jnp.zeros((), jnp.float32)), **kw((psh, one))),
        moments=jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                        **kw(psh)),
        accumulate=jax.jit(add_block, donate_argnums=(1, 2),
                           **kw((psh, one))),
        mean=jax.jit(lambda g, total, n: (jax.tree.map(lambda a: a / n, g),
                                          total / n), **kw((psh, one))),
        step=jax.jit(lambda p, g, m, v, lr, t: adam(p, g, m, v, lr, t, opt),
                     donate_argnums=(0, 2, 3), **kw((psh,) * 4)),
        sq=jax.jit(slice_sq_norms),
        change=jax.jit(_change_sq_norms))


def _change_sq_norms(after, before):
    return slice_sq_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
        after, before))


def _result(losses, grad_sq, change_sq) -> dict:
    return {"losses": losses,
            "grad_norms": {k: float(np.sqrt(x)) for k, x in grad_sq.items()},
            "change_norms": {k: float(np.sqrt(x))
                             for k, x in change_sq.items()}}


def _train_whole(dims: Dims, opt: dict, seed: int, batches, rows: int,
                 state_dtype, compute_dtype) -> dict:
    """The whole batch at once on the default device."""
    init = jax.jit(lambda: jax.tree.map(lambda a: a.astype(state_dtype),
                                        init_params(dims, seed)))
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss(p, t, y, dims, compute_dtype)))
    step_fn = jax.jit(lambda p, g, m, v, lr, t: adam(p, g, m, v, lr, t, opt),
                      donate_argnums=(0, 2, 3))
    sq = jax.jit(slice_sq_norms)
    change = jax.jit(lambda a, b: slice_sq_norms(
        jax.tree.map(lambda x, y: x.astype(jnp.float32)
                     - y.astype(jnp.float32), a, b)))

    params = init()
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    grad_sq = None
    for i, bt in enumerate(batches):
        val, grads = grad_fn(params, bt["tokens"][:rows], bt["labels"][:rows])
        losses.append(float(val))
        params, m, v, g = step_fn(params, grads, m, v,
                                  np.float32(lr_at(opt, i)),
                                  np.float32(i + 1))
        if grad_sq is None:
            grad_sq = {k: float(x) for k, x in sq(g).items()}
        del grads, g
    del m, v
    p0 = init()
    ch = {k: float(x) for k, x in change(params, p0).items()}
    return _result(losses, grad_sq, ch)
