"""Plain CIFAR-style ResNet: forward pass in straightforward ``jax.numpy``.

The reference of the ``resnet`` family of configurations (He et al. 2016 with
the 3x3 CIFAR stem and no max-pool: BasicBlock for 18/34, Bottleneck for
50/101/152). Written from the published description; imports nothing of the
program under test. Parameters are a flat ``{path: array}`` dict whose paths
are the names the program's parameter tree uses (``Conv_0/kernel``,
``BasicBlock_3/BatchNorm_1/scale``, ...): names are structure, not values.

A configuration's ``model`` group drives it:
``{"family": "resnet", "block": "basic"|"bottleneck", "stage_sizes": [...],
"stem_width": 64, "num_classes": 10, "image": [32, 32, 3]}``; its ``init``
group may hold ``"head_scale"`` and ``"residual_bn_scale"`` (`init_scales`).
"""

import jax
import jax.numpy as jnp

# The kind of input this family reads (`inputs/images.py`).
INPUT = "images"
BN_EPS = 1e-5

_BLOCKS = {"basic": ("BasicBlock", 1), "bottleneck": ("Bottleneck", 4)}


def _block_convs(model, cin, width, stride):
    """``[(suffix, kernel, cin, cout, stride, shortcut)]`` of one block: the
    main path in order, then the 1x1 projection shortcut where the block
    changes the shape; and the block's output channels."""
    _, expansion = _BLOCKS[model["block"]]
    cout = width * expansion
    if model["block"] == "basic":
        main = [(3, cin, width, stride), (3, width, width, 1)]
    else:
        main = [(1, cin, width, 1), (3, width, width, stride),
                (1, width, cout, 1)]
    convs = [(str(i), *c, False) for i, c in enumerate(main)]
    if stride != 1 or cin != cout:
        convs.append((str(len(main)), 1, cin, cout, stride, True))
    return convs, cout


def _blocks(model):
    """``(block name, its convs, stride)`` for every block in order."""
    prefix, _ = _BLOCKS[model["block"]]
    cin = model["stem_width"]
    index = 0
    for stage, nblocks in enumerate(model["stage_sizes"]):
        for i in range(nblocks):
            stride = 2 if stage > 0 and i == 0 else 1
            convs, cout = _block_convs(
                model, cin, model["stem_width"] * 2 ** stage, stride)
            yield f"{prefix}_{index}", convs, stride
            cin = cout
            index += 1


def layers(model):
    """Every conv of the model, then the dense head:
    ``(path, kernel, cin, cout, stride, in_hw)``, ``kernel == 0`` for the
    dense layer. Shapes only: `param_shapes` and `forward_macs` read it."""
    h = model["image"][0]
    out = [("Conv_0", 3, model["image"][2], model["stem_width"], 1, h)]
    cout = model["stem_width"]
    for name, convs, stride in _blocks(model):
        hw = h
        for suffix, k, ci, co, s, shortcut in convs:
            out.append((f"{name}/Conv_{suffix}", k, ci, co, s,
                        h if shortcut else hw))
            if not shortcut:
                hw //= s
            cout = co
        h //= stride
    out.append(("Dense_0", 0, cout, model["num_classes"], 1, 1))
    return out


def param_shapes(model):
    """``{path: shape}`` of every trainable leaf."""
    shapes = {}
    for path, k, cin, cout, _, _ in layers(model):
        if k == 0:
            shapes[f"{path}/kernel"] = (cin, cout)
            shapes[f"{path}/bias"] = (cout,)
            continue
        shapes[f"{path}/kernel"] = (k, k, cin, cout)
        bn = path.replace("Conv_", "BatchNorm_")
        shapes[f"{bn}/scale"] = (cout,)
        shapes[f"{bn}/bias"] = (cout,)
    return shapes


def init_scales(model, init=None):
    """``{path: factor}`` on the usual start (He-normal kernels, unit
    BatchNorm scales) where the configuration asks for a calmer one:
    ``head_scale`` on the dense head's kernel, ``residual_bn_scale`` on the
    last BatchNorm scale of every block's main path (Goyal et al. 2017 start
    it at 0; a small positive value keeps every leaf's gradient alive)."""
    init = init or {}
    scales = {"Dense_0/kernel": init.get("head_scale", 1.0)}
    for name, convs, _ in _blocks(model):
        last = [c[0] for c in convs if not c[5]][-1]
        scales[f"{name}/BatchNorm_{last}/scale"] = init.get(
            "residual_bn_scale", 1.0)
    return scales


def forward_macs(model):
    """Multiply-adds of one image's forward pass through every conv and the
    dense head (BatchNorm, ReLU, pooling and the loss are not counted)."""
    total = 0
    for _, k, cin, cout, stride, in_hw in layers(model):
        if k == 0:
            total += cin * cout
        else:
            out_hw = in_hw // stride
            total += out_hw * out_hw * k * k * cin * cout
    return total


def _conv(x, kernel, stride):
    k = kernel.shape[0]
    pad = [(1, 1), (1, 1)] if k == 3 else [(0, 0), (0, 0)]
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def _bn(x, scale, bias):
    """Training-mode batch norm over (N, H, W): biased variance."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * scale + bias


def forward(params, x, model, quant=None):
    """Logits of a batch ``x`` (N, H, W, C), BatchNorm in training mode.

    ``quant`` (the control of the correctness check) rounds every tensor a
    half-precision program rounds: both operands and the result of every
    conv and of the dense head, every BatchNorm's output, every block's
    output. None is the reference."""
    q = quant or (lambda t: t)

    def conv_bn(path, h, stride):
        bn = path.replace("Conv_", "BatchNorm_")
        h = q(_conv(q(h), q(params[f"{path}/kernel"]), stride))
        return q(_bn(h, params[f"{bn}/scale"], params[f"{bn}/bias"]))

    h = jax.nn.relu(conv_bn("Conv_0", x, 1))
    for name, convs, _ in _blocks(model):
        main = [c for c in convs if not c[5]]
        out = h
        for j, (suffix, _, _, _, s, _) in enumerate(main):
            out = conv_bn(f"{name}/Conv_{suffix}", out, s)
            if j < len(main) - 1:
                out = jax.nn.relu(out)
        for suffix, _, _, _, s, shortcut in convs:
            if shortcut:
                h = conv_bn(f"{name}/Conv_{suffix}", h, s)
        h = q(jax.nn.relu(out + h))
    h = q(jnp.mean(h, axis=(1, 2)))
    logits = jnp.dot(h, q(params["Dense_0/kernel"]),
                     precision=jax.lax.Precision.HIGHEST)
    return q(logits + params["Dense_0/bias"])
