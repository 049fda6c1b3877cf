"""Plain reference for the `resnet` family: the bottleneck ResNet of He et
al. (arXiv:1512.03385, Table 1) on 224 x 224 images, with the stride of a
down-sampling block on its 3 x 3 convolution (the "v1.5" placement that the
program's `models/resnet.py` and most public code use; the paper has it on
the first 1 x 1), batch normalisation in training mode (biased batch
variance, running averages with momentum 0.9), softmax cross-entropy.

float32 throughout, every convolution and product at `highest` precision.
It imports nothing of the program; the tree of weights it makes from the
seed carries the names the program's flax module reads.

`quant` rounds every convolution's and product's operands through a lower
precision first: the control of `correct`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUANT = {"none": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
BN_MOMENTUM, BN_EPS = 0.9, 1e-5


def _blocks(config: dict):
    """(name, filters, stride, projects) of every bottleneck block."""
    out, i = [], 0
    for stage, count in enumerate(config["stage_sizes"]):
        for j in range(count):
            out.append((f"BottleneckBlock_{i}",
                        config["num_filters"] * 2 ** stage,
                        2 if stage > 0 and j == 0 else 1, j == 0))
            i += 1
    return out


def init_params(key, config: dict) -> dict:
    """He-normal kernels, norms at scale one and bias nought (the last norm
    of a block too: at nought, as flax starts it, most leaves would get no
    gradient at the first step and the comparison would see nothing)."""
    blocks = _blocks(config)
    keys = iter(jax.random.split(key, 2 + 4 * len(blocks)))

    def conv(kh, cin, cout):
        return (jax.random.normal(next(keys), (kh, kh, cin, cout), F32)
                * np.sqrt(2.0 / (kh * kh * cin)))

    def norm(c):
        return {"scale": jnp.ones((c,), F32), "bias": jnp.zeros((c,), F32)}

    nf = config["num_filters"]
    params = {"conv_init": {"kernel": conv(7, 3, nf)}, "bn_init": norm(nf)}
    cin = nf
    for name, f, _, projects in blocks:
        b = {"Conv_0": {"kernel": conv(1, cin, f)}, "BatchNorm_0": norm(f),
             "Conv_1": {"kernel": conv(3, f, f)}, "BatchNorm_1": norm(f),
             "Conv_2": {"kernel": conv(1, f, 4 * f)},
             "BatchNorm_2": norm(4 * f)}
        if projects:
            b["conv_proj"] = {"kernel": conv(1, cin, 4 * f)}
            b["norm_proj"] = norm(4 * f)
        else:
            next(keys)
        params[name] = b
        cin = 4 * f
    params["Dense_0"] = {
        "kernel": jax.random.normal(next(keys), (cin, config["num_classes"]),
                                    F32) / np.sqrt(cin),
        "bias": jnp.zeros((config["num_classes"],), F32)}
    return params


def init_model_state(config: dict) -> dict:
    """Running mean nought and variance one for every norm."""
    def stat(c):
        return {"mean": jnp.zeros((c,), F32), "var": jnp.ones((c,), F32)}
    nf = config["num_filters"]
    out = {"bn_init": stat(nf)}
    for name, f, _, projects in _blocks(config):
        b = {"BatchNorm_0": stat(f), "BatchNorm_1": stat(f),
             "BatchNorm_2": stat(4 * f)}
        if projects:
            b["norm_proj"] = stat(4 * f)
        out[name] = b
    return out


def scale_images(images):
    """uint8 pixels to [-1, 1]; the program's loss does the same."""
    return images.astype(F32) / 127.5 - 1.0


def _q(x, quant):
    qd = QUANT[quant]
    return x if qd is None else x.astype(qd).astype(F32)


def _conv(x, kernel, stride, padding, quant):
    return jax.lax.conv_general_dilated(
        _q(x, quant), _q(kernel, quant), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")


def _norm(x, p, stat):
    """Returns (normalised, new running statistics)."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(x * x, (0, 1, 2)) - mean * mean
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * stat["mean"] + (1 - BN_MOMENTUM) * mean,
           "var": BN_MOMENTUM * stat["var"] + (1 - BN_MOMENTUM) * var}
    return y, new


def _block(stride, quant, p, stat, x):
    new = {}
    y = _conv(x, p["Conv_0"]["kernel"], 1, "SAME", quant)
    y, new["BatchNorm_0"] = _norm(y, p["BatchNorm_0"], stat["BatchNorm_0"])
    y = _conv(jax.nn.relu(y), p["Conv_1"]["kernel"], stride, "SAME", quant)
    y, new["BatchNorm_1"] = _norm(y, p["BatchNorm_1"], stat["BatchNorm_1"])
    y = _conv(jax.nn.relu(y), p["Conv_2"]["kernel"], 1, "SAME", quant)
    y, new["BatchNorm_2"] = _norm(y, p["BatchNorm_2"], stat["BatchNorm_2"])
    if "conv_proj" in p:
        x = _conv(x, p["conv_proj"]["kernel"], stride, "SAME", quant)
        x, new["norm_proj"] = _norm(x, p["norm_proj"], stat["norm_proj"])
    return jax.nn.relu(x + y), new


def batch_loss(params, mstate, batch, config: dict, quant="none"):
    """(mean cross-entropy, new running statistics) of images uint8
    [B, 224, 224, 3] and labels [B]."""
    images, labels = batch
    new = {}
    x = _conv(scale_images(images), params["conv_init"]["kernel"], 2,
              [(3, 3), (3, 3)], quant)
    x, new["bn_init"] = _norm(x, params["bn_init"], mstate["bn_init"])
    x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for name, _, stride, _ in _blocks(config):
        # a block's activations are recomputed in the backward pass: in
        # float32 at 256 images the whole net's would not fit
        x, new[name] = jax.checkpoint(
            functools.partial(_block, stride, quant))(
                params[name], mstate[name], x)
    x = jnp.mean(x, (1, 2))
    logits = jnp.einsum("bc,cn->bn", _q(x, quant),
                        _q(params["Dense_0"]["kernel"], quant),
                        precision="highest") + params["Dense_0"]["bias"]
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked), new


def loss_and_grads(params, mstate, batch, config: dict, quant="none"):
    (loss, new), grads = jax.value_and_grad(batch_loss, has_aux=True)(
        params, mstate, batch, config, quant)
    return loss, grads, new
