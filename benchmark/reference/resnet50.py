"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1), plain.

Forward pass, softmax cross-entropy, gradient and the Nesterov update
in straightforward float32 `jax.numpy` at `highest` matmul precision:
no kernels, no fusion tier, no mixed precision. Departures from the
paper, shared with the program: stride 2 sits in the first 1x1 of a
stage's first block (the paper's original placement), every convolution
carries a bias, batch-norm uses the batch's biased variance with
eps 1e-5, the update is DL4J's Nesterov form
(v' = mu v - lr g; p' = p + mu v' - lr g), no weight decay.

Also here: the operations one image requires and the bytes one step
must move, for the MFU and the step program's roofline share.
It imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

from benchmark.roofline import conv2d_flops, matmul_flops

STAGES = (("s2", (64, 64, 256), 3, 1), ("s3", (128, 128, 512), 4, 2),
          ("s4", (256, 256, 1024), 6, 2), ("s5", (512, 512, 2048), 3, 2))
BN_EPS = 1e-5


def _blocks():
    """(block name, filters, stride, has projection) in order."""
    for sname, filters, n, stride in STAGES:
        for i in range(n):
            yield f"{sname}b{i}", filters, (stride if i == 0 else 1), i == 0


def conv_specs(cfg: dict):
    """Every convolution as (name, kh, kw, c_in, c_out, out_hw)."""
    hw = int(cfg["image_size"])
    specs = [("stem", 7, 7, 3, 64, math.ceil(hw / 2))]
    hw = math.ceil(math.ceil(hw / 2) / 2)        # stem stride 2, pool 2
    c_in = 64
    for name, (f1, f2, f3), stride, proj in _blocks():
        out = math.ceil(hw / stride)
        specs.append((f"{name}_a", 1, 1, c_in, f1, out))
        specs.append((f"{name}_b", 3, 3, f1, f2, out))
        specs.append((f"{name}_c", 1, 1, f2, f3, out))
        if proj:
            specs.append((f"{name}_sc", 1, 1, c_in, f3, out))
        hw, c_in = out, f3
    return specs


def param_shapes(cfg: dict) -> dict:
    shapes = {}
    for name, kh, kw, ci, co, _ in conv_specs(cfg):
        shapes[f"{name}_conv"] = {"W": (kh, kw, ci, co), "b": (co,)}
        shapes[f"{name}_bn"] = {"gamma": (co,), "beta": (co,)}
    shapes["output"] = {"W": (2048, int(cfg["num_classes"])),
                        "b": (int(cfg["num_classes"]),)}
    return shapes


# ------------------------------------------------------------- counts
def forward_flops_per_image(cfg: dict) -> float:
    f = sum(conv2d_flops(1, o, o, co, kh, kw, ci)
            for _, kh, kw, ci, co, o in conv_specs(cfg))
    return f + matmul_flops(1, 2048, int(cfg["num_classes"]))


def train_flops_per_image(cfg: dict) -> float:
    """Forward, the gradient to every input but the image, and the
    gradient to every weight: 3x the forward less the stem's input
    gradient. 2 per MAC, nothing recomputed."""
    stem = conv_specs(cfg)[0]
    _, kh, kw, ci, co, o = stem
    return 3.0 * forward_flops_per_image(cfg) \
        - conv2d_flops(1, o, o, co, kh, kw, ci)


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for leaf in param_shapes(cfg).values()
               for s in leaf.values())


def train_step_bytes(cfg: dict, batch: int) -> float:
    """What one step must move whatever implements it: the float32
    batch once, parameters read and written, gradients once, the
    momentum read and written (float32 all)."""
    hw = int(cfg["image_size"])
    x = batch * (hw * hw * 3 + int(cfg["num_classes"])) * 4
    return x + n_params(cfg) * 4 * 5


# ---------------------------------------------------------- the model
class Rounding:
    """What a precision does to a convolution (and to the head): how it
    holds the two operands on the way forward, and how it holds the
    cotangent of the output on the way back, before the two products
    that the backward pass makes of it."""

    def __init__(self, operand=None, cotangent=None):
        self.operand, self.cotangent = operand, cotangent

    def around(self, product, x, w):
        """product(x, w) as this precision computes it."""
        import jax

        if self.operand is None:
            return product(x, w)
        operand, cotangent = self.operand, self.cotangent

        @jax.custom_vjp
        def straight(a):            # rounded forward, untouched back
            return operand(a)

        straight.defvjp(lambda a: (operand(a), None), lambda _, ct: (ct,))

        @jax.custom_vjp
        def back(y):                # untouched forward, rounded back
            return y

        back.defvjp(lambda y: (y, None), lambda _, ct: (cotangent(ct),))
        return back(product(straight(x), straight(w)))


def _scaled(dtype):
    """Round to an 8-bit float as fp8 training does (Micikevicius et
    al. 2022, arXiv:2209.05433): the tensor scaled so that its largest
    magnitude sits at the format's largest, rounded, scaled back."""
    def f(a):
        import jax.numpy as jnp

        top = float(jnp.finfo(dtype).max)
        scale = top / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        return (a * scale).astype(dtype).astype(a.dtype) / scale
    return f


def _as_bf16(a):
    import jax.numpy as jnp

    return a.astype(jnp.bfloat16).astype(a.dtype)


def _roundings():
    import jax.numpy as jnp

    return {
        # the reference: float32 operands at `highest`
        "exact": EXACT,
        # the control, one precision below the configuration's bfloat16:
        # e4m3 operands forward, e5m2 cotangents back, each tensor with
        # its own scale: the recipe that would tempt a later PR
        "fp8": Rounding(_scaled(jnp.float8_e4m3fn),
                        _scaled(jnp.float8_e5m2)),
        # a witness, no control: the program's own precision on the
        # reference's path
        "bf16": Rounding(_as_bf16, _as_bf16),
    }


EXACT = Rounding()


def _conv(x, p, stride, q):
    import jax
    from jax import lax

    def product(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)

    return q.around(product, x, p["W"]) + p["b"]


def _bn(x, p):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["gamma"] + p["beta"]


def _cbn(params, name, x, stride, q, relu=True):
    import jax

    y = _bn(_conv(x, params[f"{name}_conv"], stride, q),
            params[f"{name}_bn"])
    return jax.nn.relu(y) if relu else y


def logits_fn(params, x, q=EXACT):
    """Train-mode forward pass to the logits. `q` is the precision of
    every convolution and of the head (a `Rounding`); exact for the
    reference."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def stem(params, x):
        y = _cbn(params, "stem", x, 2, q)
        return lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")

    def block(name, stride, proj):
        def f(params, x):
            y = _cbn(params, f"{name}_a", x, stride, q)
            y = _cbn(params, f"{name}_b", y, 1, q)
            y = _cbn(params, f"{name}_c", y, 1, q, relu=False)
            sc = (_cbn(params, f"{name}_sc", x, stride, q, relu=False)
                  if proj else x)
            return jax.nn.relu(y + sc)
        return f

    # one block's activations live at a time in the backward pass, so
    # that float32 at the timed batch fits beside nothing else
    x = jax.checkpoint(stem)(params, x)
    for name, _, stride, proj in _blocks():
        sub = {k: v for k, v in params.items() if k.startswith(name + "_")}
        x = jax.checkpoint(block(name, stride, proj))(sub, x)
    x = jnp.mean(x, axis=(1, 2))
    out = params["output"]
    head = lambda a, w: jnp.matmul(  # noqa: E731
        a, w, precision=jax.lax.Precision.HIGHEST)
    return q.around(head, x, out["W"]) + out["b"]


def loss_fn(params, x, y, q=EXACT):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits_fn(params, x, q), axis=-1)
    return -jnp.mean(jnp.sum(y * logp, axis=-1))


@functools.lru_cache(maxsize=None)
def _step_fn(precision: str):
    """One jitted step for each precision, kept for the process: a study
    follows many seeds and compiles each variant once."""
    import jax

    q = _roundings()[precision]

    @jax.jit
    def step(params, v, x, y, lr, momentum):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y, q)
        v = jax.tree_util.tree_map(lambda v, g: momentum * v - lr * g, v, g)
        params = jax.tree_util.tree_map(
            lambda p, v, g: p + momentum * v - lr * g, params, v, g)
        return params, v, loss, g

    return step


def train_steps(params, batches, lr: float, momentum: float,
                precision: str = "exact"):
    """Follow the first len(batches) steps. Returns (losses, the first
    gradient's leaves, the leaves of the parameters' change over all
    the steps), the leaves as numpy arrays in `leaf_names` order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.correct import host_leaves

    step = _step_fn(precision)
    p0 = params
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, g1 = [], None
    for x, y in batches:
        params, v, loss, g = step(params, v, jnp.asarray(x), jnp.asarray(y),
                                  jnp.float32(lr), jnp.float32(momentum))
        losses.append(float(loss))
        if g1 is None:
            g1 = host_leaves(g)
        del g
    change = host_leaves(jax.jit(lambda a, b: jax.tree_util.tree_map(
        jnp.subtract, a, b))(params, p0))
    return np.asarray(losses), g1, change
