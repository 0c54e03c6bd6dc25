"""Plain float32 reference of the convnet family.

Straightforward ``jax.numpy`` / ``lax`` from a configuration's layer
list: ``lax.conv_general_dilated``, ``reduce_window``, the AlexNet
local response normalization, dense layers and the softmax
cross-entropy, in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32
contraction otherwise runs in bf16 passes). No unit of the program is
imported: the layer descriptors and the parameter arrays are all it
takes from the system under test (``pair`` is the yardstick's reader
of a descriptor's ``sliding``).

Departures from a textbook network, all of them the upstream Znicz
conventions that the configuration files state: LRN uses k=2 unless
the layer says otherwise; "str" layers are max(x, 0); average pooling
divides by the full window; the dense layers see the NHWC activations
flattened row-major; dropout is the identity outside training.
"""

import jax
import jax.numpy as jnp
import numpy

from benchmark.flops import pair

#: Largest |program - reference| allowed on a validation batch's mean
#: loss with untrained weights, whatever the program's compute policy.
#: Under ``bfloat16`` activations are rounded to 8 bits of mantissa
#: between layers while parameters and accumulation stay float32; with
#: untrained weights the logits are ~1e-2, a logit moves by ~4e-5 and
#: a batch mean of 128 losses by a few 1e-6: on the v5e the AlexNet
#: cells measured 0.9e-6 to 1.2e-6 (PR 22), and float32 on the CPU
#: 3e-7. The bound is ~20 times that. The other side: the batch means
#: themselves spread by 4e-4 to 7e-4 around ln(classes) (``agreement``
#: reports the spread beside the error), so a network whose
#: activations are wrong - another layer order, a missing LRN, int8
#: where bf16 is stated - misses the bound by an order of magnitude.
LOSS_TOLERANCE = 2e-5

#: samples to a reference call: float32 activations of AlexNet take
#: ~3 MB a sample, so 32 keeps the reference far below the program's
#: own peak and out of ``peak_hbm_mb``
CHUNK = 32

#: by the suffix of the layer type; a configuration with another
#: activation (Znicz "tanh" is 1.7159 tanh(0.6666 x), its plain "relu"
#: is softplus) adds it here with its formula
ACTIVATIONS = {
    "linear": lambda x: x,
    "str": lambda x: jnp.maximum(x, 0.0),
}


def _activation(ltype):
    suffix = ltype.split("_", 1)[1] if "_" in ltype else "linear"
    return ACTIVATIONS[suffix]


def _lrn(x, n, alpha, beta, k):
    half = n // 2
    padded = jnp.pad(jnp.square(x), [(0, 0)] * 3 + [(half, half)])
    window = jax.lax.reduce_window(
        padded, 0.0, jax.lax.add, (1, 1, 1, n), (1, 1, 1, 1), "VALID")
    return x / (k + alpha * window) ** beta


def logits(layers, params, x):
    """Forward pass in evaluation mode. ``params[i]`` is layer i's
    ``{"weights", "bias"}`` (empty for layers without parameters)."""
    x = x.astype(jnp.float32)
    for descr, p in zip(layers, params):
        ltype = descr["type"]
        if ltype.startswith("conv"):
            sx, sy = pair(descr.get("sliding"), (1, 1))
            pad = descr.get("padding", 0)
            x = jax.lax.conv_general_dilated(
                x, p["weights"], window_strides=(sy, sx),
                padding=((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if "bias" in p:
                x = x + p["bias"]
            x = _activation(ltype)(x)
        elif ltype.startswith("all2all") or ltype == "softmax":
            x = x.reshape(x.shape[0], -1) @ p["weights"]
            if "bias" in p:
                x = x + p["bias"]
            if ltype != "softmax":  # the head stays at logits
                x = _activation(ltype)(x)
        elif ltype in ("max_pooling", "avg_pooling"):
            sx, sy = pair(descr.get("sliding"),
                           (descr["kx"], descr["ky"]))
            window = (1, descr["ky"], descr["kx"], 1)
            if ltype == "max_pooling":
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, window, (1, sy, sx, 1),
                    "VALID")
            else:
                x = jax.lax.reduce_window(
                    x, 0.0, jax.lax.add, window, (1, sy, sx, 1),
                    "VALID") / (descr["kx"] * descr["ky"])
        elif ltype == "norm":
            x = _lrn(x, descr.get("n", 5), descr.get("alpha", 1e-4),
                     descr.get("beta", 0.75), descr.get("k", 2.0))
        elif ltype == "dropout":
            pass
        else:
            raise ValueError("the reference has no layer %r" % ltype)
    return x


def sample_losses(layers, params, data, labels):
    """Per-sample cross-entropy of ``data`` (host arrays), computed
    ``CHUNK`` samples at a time and kept in float64 on the host."""
    layers = [dict(d) for d in layers]

    @jax.jit
    def chunk_losses(params, x, y):
        logp = jax.nn.log_softmax(logits(layers, params, x))
        return -jnp.take_along_axis(logp, y[:, None], 1)[:, 0]

    out = numpy.empty(len(data), numpy.float64)
    with jax.default_matmul_precision("highest"):
        params = jax.device_put(params)
        for start in range(0, len(data), CHUNK):
            stop = min(start + CHUNK, len(data))
            out[start:stop] = chunk_losses(
                params, numpy.asarray(data[start:stop], numpy.float32),
                numpy.asarray(labels[start:stop]))
    return out


def validation_batch_losses(layers, params, data, labels, batch):
    """What the program's validation sweep reports from these
    parameters: the mean loss of each batch of ``batch`` samples, in
    the order served (validation is never shuffled)."""
    per_sample = sample_losses(layers, params, data, labels)
    return numpy.array([per_sample[i:i + batch].mean()
                        for i in range(0, len(per_sample), batch)])


def agreement(program_losses, reference_losses):
    """``(ok, report)`` for the two per-batch loss vectors."""
    program = numpy.asarray(program_losses, numpy.float64)
    reference = numpy.asarray(reference_losses, numpy.float64)
    if program.shape != reference.shape:
        return False, {"error": "shapes %s vs %s"
                       % (program.shape, reference.shape)}
    err = float(numpy.max(numpy.abs(program - reference)))
    return bool(err <= LOSS_TOLERANCE), {
        "max_abs_error": err, "tolerance": LOSS_TOLERANCE,
        "batch_mean_spread": float(numpy.std(reference)),
        "batches": int(reference.size)}
