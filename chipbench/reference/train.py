"""A few Adam steps of the plain reference, layer by layer, in float32.

``train`` starts from the weights that ``model.init_params`` makes from
the run's key for the configuration's block family (the program is given
the same), takes ``len(batches)`` steps of Adam (Kingma & Ba, algorithm
1, with the step size folded into alpha_t) and returns what the check
compares: each step's loss, every layer leaf's gradient norm at the
first step, and every leaf's change after the last.  Layers are numbered
through the family's layer groups in order.

The loss is the masked cross-entropy's sum over its weight W plus, for
each layer, the mean over its blocks of rows of the layer's auxiliary
loss, as the program adds the mean over its microbatches.

It is plain, not small: it works layer by layer, each layer's forward
and backward pass over blocks of rows, and where the float32 weights and
Adam's slots would not leave room on the device, the layers' slots wait
in host memory between their updates, so a model that fills HBM can
still be checked at the timed batch.  The backward pass recomputes each
layer from its stored input.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from chipbench import cell
from chipbench.reference import model as M

TOKENS_PER_ROW_BLOCK = 8192
TOKENS_PER_HEAD_BLOCK = 2048
Q_BLOCK = 512
# above this many bytes of layer weights + Adam slots, the slots are parked
PARK_ABOVE_BYTES = 8e9


class _Keep:
    def put(self, tree):
        return tree

    def get(self, tree):
        return tree


class Store:
    """Host memory, where the reference parks what it cannot hold on the
    device (``pinned_host`` on a TPU; nothing moves on the CPU).  Each
    move is a compiled copy, which runs as one DMA per array."""

    def __init__(self):
        dev = jax.devices()[0]
        self._put = self._get = lambda tree: tree
        if dev.platform == "tpu":
            host = SingleDeviceSharding(dev, memory_kind="pinned_host")
            self._put = jax.jit(lambda t: t, out_shardings=host)
            self._get = jax.jit(lambda t: t,
                                out_shardings=SingleDeviceSharding(dev))

    def put(self, tree):
        return self._put(tree)

    def get(self, tree):
        return self._get(tree)


class Reference:
    """The reference's compiled pieces for one configuration file and
    shape."""

    def __init__(self, conf: dict, batch: int, seq: int,
                 arith: str = "float32"):
        fam = cell.family(conf)
        c, opt = conf["config"], conf["optimizer"]
        head_loss_sum = getattr(fam, "head_loss_sum", M.head_loss_sum)
        ar = M.Arith(arith)
        rb = max(1, min(batch, TOKENS_PER_ROW_BLOCK // seq))
        while batch % rb:
            rb -= 1
        qb = min(seq, Q_BLOCK)
        tb = min(batch * seq, TOKENS_PER_HEAD_BLOCK)

        def blocks(x, n):
            return x.reshape((x.shape[0] // n, n) + x.shape[1:])

        def fwd(w, x, group):
            ys, aux = jax.lax.map(
                lambda xb: fam.layer(c, ar, w, xb, qb, group), blocks(x, rb))
            return ys.reshape(x.shape), aux.mean()

        def bwd(w, x, dy, scale, group):
            # dy is the gradient of the cross-entropy's sum, which ``scale``
            # (1/W) turns into the loss's; a block's aux enters the loss
            # with weight 1/blocks
            aux_ct = 1.0 / (scale * (x.shape[0] // rb))

            def body(dw, xy):
                _, vjp = jax.vjp(
                    lambda w_, x_: fam.layer(c, ar, w_, x_, qb, group),
                    w, xy[0])
                dw_b, dx_b = vjp((xy[1], aux_ct))
                return jax.tree.map(jnp.add, dw, dw_b), dx_b
            zero = jax.tree.map(jnp.zeros_like, w)
            dw, dx = jax.lax.scan(body, zero, (blocks(x, rb), blocks(dy, rb)))
            return jax.tree.map(lambda g: g * scale, dw), dx.reshape(x.shape)

        def head(static, x, targets, mask):
            d = x.shape[-1]

            def body(carry, blk):
                xb, tb_, mb = blk
                loss, (gs, gx) = jax.value_and_grad(
                    lambda s, xx: head_loss_sum(c, ar, s, xx, tb_, mb),
                    argnums=(0, 1))(static, xb)
                return (carry[0] + loss,
                        jax.tree.map(jnp.add, carry[1], gs)), gx
            zero = jax.tree.map(jnp.zeros_like, static)
            (loss, gs), dx = jax.lax.scan(
                body, (jnp.float32(0), zero),
                (blocks(x.reshape(-1, d), tb), blocks(targets.reshape(-1), tb),
                 blocks(mask.reshape(-1), tb)))
            return loss, gs, dx.reshape(x.shape)

        def static_grad(g_static, ids, dx, scale):
            """The head's gradient plus the embedding rows' (scatter-add of
            the first layer's input gradient), over the mask's weight."""
            d = dx.shape[-1]
            g = jax.tree.map(lambda a: a * scale, g_static)
            rows = jnp.zeros_like(g["embed"]["tok"]).at[ids.reshape(-1)].add(
                dx.reshape(-1, d))
            g["embed"]["tok"] = g["embed"]["tok"] + rows * scale
            return g

        def adam(p, g, m, v, t):
            b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
            a = opt["lr"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
            p = jax.tree.map(lambda p_, m_, v_: p_ - a * m_ / (jnp.sqrt(v_)
                                                              + eps), p, m, v)
            return p, m, v

        self.embed = jax.jit(lambda tok, ids: tok[ids])
        self.fwd = jax.jit(fwd, static_argnames="group")
        self.bwd = jax.jit(bwd, static_argnames="group")
        self.head = jax.jit(head)
        self.static_grad = jax.jit(static_grad)
        self.adam = jax.jit(adam, donate_argnums=(0, 2, 3))
        self.tree_norms = jax.jit(lambda t: jax.tree.map(jnp.linalg.norm, t))
        self.diff_norms = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: jnp.linalg.norm(x - y), a, b))


def initial_params(fam, c: dict, key) -> tuple:
    """(static, layers, groups): the embedding and head as one tree, one
    tree per layer through the layer groups in order, all on the device,
    from ``model.init_params``, and each layer's group."""
    p = M.make_params(fam, c, key)
    layers, groups = [], []
    for g, stacked in enumerate(p.pop("groups")):
        rows = jax.tree.leaves(stacked)[0].shape[0]
        layers += [jax.tree.map(lambda a, i=i: a[i], stacked)
                   for i in range(rows)]
        groups += [g] * rows
    return p, layers, groups


def _named(tree, prefix) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[prefix + ".".join(str(p.key) for p in path)] = float(leaf)
    return out


def _tree_bytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def train(conf: dict, key, batches: list, arith: str = "float32",
          ref: Reference | None = None) -> dict:
    """Readings of ``len(batches)`` reference steps from the init, for
    configuration file ``conf``."""
    B, S = batches[0]["tokens"].shape
    ref = ref or Reference(conf, B, S, arith)
    fam, c = cell.family(conf), conf["config"]
    static, params, groups = initial_params(fam, c, key)
    n = len(params)
    # Adam's slots of the layers, and the layers' inputs, wait in host
    # memory where they would not leave the device room to work in
    layer_bytes = _tree_bytes(params)
    park = Store() if 3 * layer_bytes > PARK_ABOVE_BYTES else _Keep()
    x_bytes = B * S * c["hidden_size"] * 4 * n
    park_x = Store() if x_bytes > PARK_ABOVE_BYTES / 2 else _Keep()
    m = [park.put(jax.tree.map(jnp.zeros_like, w)) for w in params]
    v = [park.put(jax.tree.map(jnp.zeros_like, w)) for w in params]
    sm = jax.tree.map(jnp.zeros_like, static)
    sv = jax.tree.map(jnp.zeros_like, static)
    losses, grads = [], {}
    for t, b in enumerate(batches, start=1):
        ids = jnp.asarray(b["tokens"])
        W = float(np.sum(b["mask"]))
        x = ref.embed(static["embed"]["tok"], ids)
        xs, aux = [], []
        for i in range(n):
            xs.append(park_x.put(x))
            x, aux_i = ref.fwd(params[i], x, group=groups[i])
            aux.append(aux_i)
        loss, g_static, dx = ref.head(static, x, jnp.asarray(b["targets"]),
                                      jnp.asarray(b["mask"]))
        losses.append(float(loss) / W + sum(float(a) for a in aux))
        del x
        for i in reversed(range(n)):
            dw, dx = ref.bwd(params[i], park_x.get(xs.pop()), dx,
                             jnp.float32(1.0 / W), group=groups[i])
            if t == 1:
                grads.update(_named(ref.tree_norms(dw), f"layers.{i}."))
            params[i], mi, vi = ref.adam(params[i], dw, park.get(m[i]),
                                         park.get(v[i]), jnp.float32(t))
            m[i], v[i] = park.put(mi), park.put(vi)
            del dw, mi, vi
        g_static = ref.static_grad(g_static, ids, dx, jnp.float32(1.0 / W))
        if t == 1:
            grads.update(_named(ref.tree_norms(g_static), ""))
        static, sm, sv = ref.adam(static, g_static, sm, sv, jnp.float32(t))
        del dx, g_static
    del m, v, sm, sv
    static0, params0, _ = initial_params(fam, c, key)
    change = _named(ref.diff_norms(static, static0), "")
    for i in range(n):
        change.update(_named(ref.diff_norms(params[i], params0[i]),
                             f"layers.{i}."))
    return {"losses": losses, "grad_norms": grads, "change_norms": change}
