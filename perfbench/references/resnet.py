"""Plain reference of the bottleneck ResNet (arXiv:1512.03385, Table 1)
in train mode: float32 ``jax.numpy`` with every contraction at
``Precision.HIGHEST``, batch statistics over the rank's own batch, no
kernels, no space-to-depth stem, and no import from ``bluefog_tpu``.
A convolution is written out as what it is, one matrix product over the
patches under each output pixel (the TPU compiler's own float32
convolution at HIGHEST ran 17 times slower than its matrix product: my
chip run, PR 23).
Weights come in as data under the names ``families/resnet.make_params``
gives them.

Departures from the paper: the stride of a stage's first bottleneck
sits on its 3x3 convolution, which pads "SAME" (one row and column
at the far edge only, where torchvision pads one all round); the
configuration states both.  Each
bottleneck is rematerialised in the backward pass only so that float32
activations of a 128-image batch fit on one chip.

``conv(x, w, stride, padding)`` is the one contraction everything
goes through, so that the output check can put a lower precision in
its place; ``mm_highest`` / ``mm_control`` name the two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_highest(x, w, stride, padding):
    """Convolution NHWC x HWIO -> NHWC.  ``padding``: ``"VALID"``,
    ``"SAME"`` (the far edge gets the odd row) or explicit pairs."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    kh, kw, cin, cout = w.shape
    n, h, wd, _ = x.shape
    if padding == "VALID":
        pads = [(0, 0), (0, 0)]
    elif padding == "SAME":
        pads = []
        for size, k in ((h, kh), (wd, kw)):
            total = max((-(-size // stride) - 1) * stride + k - size, 0)
            pads.append((total // 2, total - total // 2))
    else:
        pads = list(padding)
    x = jnp.pad(x, [(0, 0), pads[0], pads[1], (0, 0)])
    ho = (x.shape[1] - kh) // stride + 1
    wo = (x.shape[2] - kw) // stride + 1
    patches = jnp.concatenate(
        [x[:, i:i + stride * (ho - 1) + 1:stride,
           j:j + stride * (wo - 1) + 1:stride, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    return jnp.einsum("nhwk,kd->nhwd", patches,
                      w.reshape(kh * kw * cin, cout), precision=HIGHEST)


def _fake_fp8(x):
    """Round to float8 e4m3 with one scale per tensor and back; the
    gradient passes straight through the rounding."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm_control(x, w, stride, padding):
    """The control: operands rounded to fp8 e4m3 (one scale a tensor),
    the precision below bfloat16; products accumulated exactly."""
    return mm_highest(_fake_fp8(x), _fake_fp8(w), stride, padding)


def batch_norm(x, p, stats, sz):
    """Train mode: normalise by the batch's own mean and (biased)
    variance; the running statistics move by ``1 - momentum``."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x), (0, 1, 2)) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(var + sz["bn_epsilon"]) * p["scale"] \
        + p["bias"]
    m = sz["bn_momentum"]
    return y, {"mean": m * stats["mean"] + (1 - m) * mean,
               "var": m * stats["var"] + (1 - m) * var}


def bottleneck(x, p, stats, stride, sz, conv):
    new = {}
    y = conv(x, p["Conv_0"]["kernel"], 1, "VALID")
    y, new["BatchNorm_0"] = batch_norm(y, p["BatchNorm_0"],
                                       stats["BatchNorm_0"], sz)
    y = conv(jax.nn.relu(y), p["Conv_1"]["kernel"], stride, "SAME")
    y, new["BatchNorm_1"] = batch_norm(y, p["BatchNorm_1"],
                                       stats["BatchNorm_1"], sz)
    y = conv(jax.nn.relu(y), p["Conv_2"]["kernel"], 1, "VALID")
    y, new["BatchNorm_2"] = batch_norm(y, p["BatchNorm_2"],
                                       stats["BatchNorm_2"], sz)
    if "conv_proj" in p:
        x = conv(x, p["conv_proj"]["kernel"], stride, "VALID")
        x, new["norm_proj"] = batch_norm(x, p["norm_proj"],
                                         stats["norm_proj"], sz)
    return jax.nn.relu(x + y), new


def forward(params, stats, images, sz, conv):
    """Logits ``[batch, classes]`` and the new running statistics."""
    new = {}
    x = conv(images, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x, new["bn_init"] = batch_norm(x, params["bn_init"], stats["bn_init"],
                                   sz)
    x = jax.lax.reduce_window(
        jax.nn.relu(x), -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    idx = 0
    for i, count in enumerate(sz["stage_sizes"]):
        for j in range(count):
            name = f"BottleneckBlock_{idx}"
            stride = 2 if i > 0 and j == 0 else 1
            x, new[name] = jax.checkpoint(functools.partial(
                bottleneck, stride=stride, sz=sz, conv=conv))(
                    x, params[name], stats[name])
            idx += 1
    x = jnp.mean(x, (1, 2))
    logits = jnp.matmul(x, params["Dense_0"]["kernel"], precision=HIGHEST) \
        + params["Dense_0"]["bias"]
    return logits, new


def loss(params, aux, batch, sz, mm=mm_highest):
    """Mean softmax cross-entropy of one rank's batch; ``(loss, new
    batch statistics)``."""
    images, labels = batch
    logits, new = forward(params, aux, images.astype(jnp.float32), sz, mm)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked), new


# ------------------------------------------------------------------ #
# operations, from shapes alone
# ------------------------------------------------------------------ #
def forward_flops_per_image(sz: dict) -> float:
    """2 FLOPs a multiply-add over every convolution and the head
    (batch norm, ReLU and pooling are not counted)."""
    def conv(k, cin, cout, out):
        return 2.0 * k * k * cin * cout * out * out

    f, s = sz["num_filters"], sz["image_size"]
    s //= 2
    total = conv(7, 3, f, s)
    s //= 2
    cin = f
    for i, count in enumerate(sz["stage_sizes"]):
        width = f * 2 ** i
        cout = width * sz["expansion"]
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            total += conv(1, cin, width, s)
            s //= stride
            total += conv(3, width, width, s) + conv(1, width, cout, s)
            if stride != 1 or cin != cout:
                total += conv(1, cin, cout, s)
            cin = cout
    return total + 2.0 * cin * sz["num_classes"]


def train_flops_per_item(sz: dict, traffic: dict) -> float:
    """Forward + backward: three times forward (the stem's input
    gradient, which nothing needs, counted with the rest: under 1%)."""
    return 3.0 * forward_flops_per_image(sz)
