"""Inference package writer (``veles/workflow.py:868-975``).

Package layout (uncompressed POSIX tar, or a plain directory)::

    contents.json        workflow name/checksum + ordered unit chain
    @0000_64x10.npy      array members referenced from contents.json
    ...
    model.stablehlo      optional jax.export artifact (PJRT deployment)

Array-valued properties appear in ``contents.json`` as ``@NNNN_shape``
strings — the reference's NumpyArrayReference convention
(``libVeles/src/main_file_loader.h:46-63``) — resolved against same-
named ``.npy`` members. The native runtime (``native/``) consumes
exactly this format; ``tests/test_export.py`` round-trips it.
"""

import io
import json
import os
import tarfile

import numpy

#: forward-unit classes the package format covers, with the properties
#: each contributes. Array props are exported as member references.
_EXPORTERS = {}


def exporter(*class_names):
    def register(fn):
        for name in class_names:
            _EXPORTERS[name] = fn
        return fn
    return register


def _common(unit):
    data = {}
    if getattr(unit, "weights", None) is not None \
            and unit.has_weights and unit.weights.mem is not None:
        # map_read(): training updates live device-side; the host mirror
        # is stale until explicitly synced
        data["weights"] = numpy.asarray(unit.weights.map_read(),
                                        numpy.float32)
        if unit.include_bias and unit.bias.mem is not None:
            data["bias"] = numpy.asarray(unit.bias.map_read(),
                                         numpy.float32)
    return data


@exporter("All2All", "All2AllTanh", "All2AllRELU", "All2AllStrictRELU",
          "All2AllSigmoid")
def _export_all2all(unit):
    data = _common(unit)
    data["activation"] = unit.activation_name
    data["output_sample_shape"] = list(unit.output_sample_shape)
    return data


@exporter("All2AllSoftmax")
def _export_softmax(unit):
    data = _common(unit)
    data["activation"] = "softmax"
    data["output_sample_shape"] = list(unit.output_sample_shape)
    return data


#: activations the native Conv kernel can apply per-scalar; sincos
#: needs channel indices and is only wired for All2All/ActivationUnit
_CONV_ACTIVATIONS = ("linear", "tanh", "sigmoid", "relu", "strict_relu",
                     "leaky_relu", "log")


@exporter("Conv", "ConvTanh", "ConvRELU", "ConvStrictRELU", "ConvSigmoid")
def _export_conv(unit):
    data = _common(unit)
    if unit.activation_name not in _CONV_ACTIVATIONS:
        raise NotImplementedError(
            "Conv activation %r is not supported by the native runtime"
            % unit.activation_name)
    data["activation"] = unit.activation_name
    data["n_kernels"] = unit.n_kernels
    data["kx"], data["ky"] = unit.kx, unit.ky
    data["sliding"] = list(unit.sliding)
    pads = unit._pad_pairs()
    if isinstance(pads, str):
        data["padding"] = pads
    else:
        (top, bottom), (left, right) = pads
        data["padding"] = [left, top, right, bottom]
    return data


@exporter("MaxPooling", "MaxAbsPooling", "AvgPooling")
def _export_pooling(unit):
    return {"kx": unit.kx, "ky": unit.ky, "sliding": list(unit.sliding)}


@exporter("LRNormalizerForward")
def _export_lrn(unit):
    return {"k": unit.k, "alpha": unit.alpha, "beta": unit.beta,
            "n": unit.n}


@exporter("ActivationUnit")
def _export_activation(unit):
    return {"activation": unit.activation_name}


@exporter("DropoutForward")
def _export_dropout(unit):
    # inference: inverted dropout is identity
    return {"identity": True}


@exporter("MoEForward")
def _export_moe(unit):
    data = _common(unit)   # router rides as "weights" (dim, E)
    data["up"] = numpy.asarray(unit.up.map_read(), numpy.float32)
    data["down"] = numpy.asarray(unit.down.map_read(), numpy.float32)
    data["n_experts"] = int(unit.n_experts)
    data["capacity_factor"] = float(unit.capacity_factor)
    data["residual"] = int(bool(unit.residual))
    return data


@exporter("MultiHeadAttentionForward")
def _export_attention(unit):
    data = _common(unit)   # weights (4, D, D) + bias (4, D)
    data["heads"] = int(unit.heads)
    # booleans ride as 0/1: the native JSON reader's numeric accessor
    data["causal"] = int(bool(unit.causal))
    data["residual"] = int(bool(unit.residual))
    return data


@exporter("TokenEmbeddingForward")
def _export_token_embedding(unit):
    data = _common(unit)   # the table (vocabulary, dim)
    data.update(vocabulary=unit.vocabulary, dim=unit.dim,
                positions=unit.positions)
    return data


@exporter("VocabularyHeadForward")
def _export_vocabulary_head(unit):
    # a tied head brings the name of the embedding whose table it
    # reads and no array: the package holds the table once
    data = _common(unit)
    data["vocabulary"] = unit.vocabulary
    if unit.tied_to:
        data["tied_to"] = unit.tied_to
    return data


class _MemberWriter(object):
    """Allocates @NNNN_shape member names and collects npy blobs."""

    def __init__(self, precision):
        self.members = {}
        self.dtype = numpy.dtype(precision)

    def ref(self, array):
        array = numpy.ascontiguousarray(array, self.dtype)
        name = "@%04d_%s" % (len(self.members),
                             "x".join(str(d) for d in array.shape))
        buf = io.BytesIO()
        numpy.save(buf, array, allow_pickle=False)
        self.members[name] = buf.getvalue()
        return name


def _unit_entry(unit, writer):
    cls_name = type(unit).__name__
    export_fn = _EXPORTERS.get(cls_name)
    if export_fn is None:
        raise NotImplementedError(
            "%s is not exportable (supported: %s)" %
            (cls_name, sorted(_EXPORTERS)))
    data = export_fn(unit)
    for key, value in list(data.items()):
        if isinstance(value, numpy.ndarray):
            data[key] = writer.ref(value)
    return {"class": {"name": cls_name,
                      "uuid": getattr(type(unit), "__id__", None)},
            "data": data}


def _stablehlo_blob(workflow, input_shape, precision):
    """Serialized jax.export artifact of the forward chain (optional)."""
    try:
        import jax
        import jax.numpy as jnp
        from jax import export as jax_export
    except ImportError:
        return None
    forwards = workflow.forwards
    # inference artifact: dropout & co. must trace as identity, not
    # bake in the last training-step mask
    saved_testing = [(f, f.testing) for f in forwards
                     if hasattr(f, "testing")]
    for fwd, _ in saved_testing:
        fwd.testing = True

    def forward(params, x):
        for fwd, p in zip(forwards, params):
            x = fwd.apply(p, x)
        return x

    try:
        params = tuple(
            {k: jnp.asarray(v) for k, v in fwd.param_values().items()}
            if fwd.has_weights else {}
            for fwd in forwards)
        param_shapes = tuple(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         p)
            for p in params)
        sample_shape = tuple(input_shape[1:])
        dtype = jnp.dtype(precision)
        try:
            # symbolic batch: the artifact must serve ANY batch size,
            # not just the training minibatch it was exported from
            (b,) = jax_export.symbolic_shape("b")
            x = jax.ShapeDtypeStruct((b,) + sample_shape, dtype)
            exported = jax_export.export(jax.jit(forward))(param_shapes, x)
        except Exception:
            x = jax.ShapeDtypeStruct(tuple(input_shape), dtype)
            exported = jax_export.export(jax.jit(forward))(param_shapes, x)
        return exported.serialize()
    except Exception:
        return None
    finally:
        for fwd, state in saved_testing:
            fwd.testing = state


def export_workflow(workflow, path, precision="float32"):
    """Write the inference package for ``workflow`` to ``path``.

    ``path`` ending in ``.tar`` → one uncompressed tar; otherwise a
    directory is populated. Returns the path.
    """
    forwards = getattr(workflow, "forwards", None)
    if not forwards:
        raise ValueError("workflow has no forwards chain to export")
    writer = _MemberWriter(precision)
    units = [_unit_entry(unit, writer) for unit in forwards]
    loader = getattr(workflow, "loader", None)
    input_shape = None
    if loader is not None and loader.minibatch_data.mem is not None:
        input_shape = list(loader.minibatch_data.shape)
    contents = {
        "workflow": {
            "name": workflow.name,
            "checksum": workflow.checksum,
            "units": units,
        },
        "input_shape": input_shape,
        "precision": str(numpy.dtype(precision)),
        "format_version": 1,
    }
    blob = None
    if input_shape:
        blob = _stablehlo_blob(workflow, input_shape, precision)
    members = dict(writer.members)
    members["contents.json"] = json.dumps(
        contents, indent=2, sort_keys=True).encode("utf-8")
    if blob:
        members["model.stablehlo"] = blob

    if str(path).endswith(".tar"):
        with tarfile.open(path, "w") as tar:
            for name in sorted(members):
                if name.startswith("@"):
                    name_on_disk = name + ".npy"
                else:
                    name_on_disk = name
                info = tarfile.TarInfo(name_on_disk)
                info.size = len(members[name])
                tar.addfile(info, io.BytesIO(members[name]))
    else:
        os.makedirs(path, exist_ok=True)
        for name, data in members.items():
            name_on_disk = name + ".npy" if name.startswith("@") else name
            with open(os.path.join(path, name_on_disk), "wb") as f:
                f.write(data)
    return path


def load_package_info(path):
    """Read back contents.json (+ member list) for inspection/tests."""
    if os.path.isdir(path):
        with open(os.path.join(path, "contents.json"), "rb") as f:
            contents = json.loads(f.read())
        members = sorted(os.listdir(path))
    else:
        with tarfile.open(path, "r") as tar:
            members = sorted(tar.getnames())
            contents = json.loads(
                tar.extractfile("contents.json").read())
    return contents, members
