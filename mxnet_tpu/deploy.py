"""mx.deploy — self-contained inference artifacts.

Reference analogue: the C predict API (include/mxnet/c_predict_api.h,
src/c_api/c_predict_api.cc) + amalgamation: deploy a trained model
where the framework is not installed. The TPU-native equivalent
serializes the jitted forward as a StableHLO artifact via ``jax.export``
with the parameters baked in as constants — the loader needs ONLY jax
(any backend: CPU, TPU), not mxnet_tpu, matching the role of the
reference's dependency-free predictor:

    mx.deploy.export_predictor(net, example, "model.mxtpu")
    # ... on the serving side (only jax installed):
    from jax import export
    blob = open("model.mxtpu", "rb").read()[HEADER:]
    out = export.deserialize(blob).call(x)

The file format is a small JSON header (input/output specs) + the
serialized artifact; ``load_predictor`` reads it back and ``Predictor``
calls it on numpy arrays.
"""
from __future__ import annotations

import json
import struct

import numpy as _np

__all__ = ["export_predictor", "load_predictor", "Predictor",
           "export_decoder", "load_decoder",
           "flatten_params", "unflatten_params", "params_from_arrays"]

_MAGIC = b"MXTPUPRED1"
_LLM_MAGIC = b"MXTPULLM01"


def export_predictor(net, example_input, path=None, training=False,
                     poly_batch=False):
    """Serialize a gluon block's forward (params baked in) to a
    self-contained artifact. ``example_input``: NDArray/ndarray fixing
    the input shape/dtype. Returns the bytes; writes ``path`` if given.

    With ``poly_batch=True`` the leading (batch) dimension is exported
    symbolically (``jax.export`` shape polymorphism): the loaded
    predictor then accepts ANY batch size, compiling once per distinct
    size it sees — the property ``mxnet_tpu.serving`` relies on to run
    a fixed bucket set with zero steady-state recompiles.
    """
    import jax
    from jax import export as jexport
    import jax.numpy as jnp
    from .ndarray import NDArray
    from .parallel import functional_call, extract_params
    from . import autograd

    x = example_input._data if isinstance(example_input, NDArray) \
        else jnp.asarray(example_input)
    with autograd.pause(train_mode=False):
        net(NDArray(x[:1]))                 # resolve deferred shapes
    params = {k: v for k, v in extract_params(net).items()}

    def fwd(inp):
        out, _ = functional_call(net, params, inp, training=training)
        return out

    spec_shape = x.shape
    if poly_batch:
        spec_shape = tuple(jexport.symbolic_shape("b")) \
            + tuple(x.shape[1:])
    exp = jexport.export(jax.jit(fwd))(
        jax.ShapeDtypeStruct(spec_shape, x.dtype))
    blob = exp.serialize()
    header = json.dumps({
        "input_shape": list(x.shape), "input_dtype": str(x.dtype),
        "poly_batch": bool(poly_batch),
        "format": "jax.export/stablehlo",
    }).encode()
    artifact = _MAGIC + struct.pack("<I", len(header)) + header + blob
    if path:
        with open(path, "wb") as f:
            f.write(artifact)
    return artifact


class Predictor:
    """Loaded artifact (reference: MXPredCreate/MXPredForward).

    The exported computation is wrapped in ONE ``jax.jit`` at load
    time, so repeated ``predict`` calls hit the jit cache instead of
    re-tracing the deserialized module per call — the difference
    between a serving path and a demo. ``donate_input=True`` lets XLA
    reuse the input buffer's device memory for outputs (worth it for
    large activations on accelerators; some backends cannot honor it
    and fall back with a warning).
    """

    def __init__(self, artifact, donate_input=False):
        import jax
        from jax import export as jexport
        if isinstance(artifact, str):
            with open(artifact, "rb") as f:
                artifact = f.read()
        if not artifact.startswith(_MAGIC):
            raise ValueError("not an mxnet_tpu predictor artifact")
        off = len(_MAGIC)
        (hlen,) = struct.unpack_from("<I", artifact, off)
        off += 4
        self.meta = json.loads(artifact[off:off + hlen].decode())
        self._exported = jexport.deserialize(artifact[off + hlen:])
        self._call = jax.jit(
            self._exported.call,
            donate_argnums=(0,) if donate_input else ())

    @property
    def input_shape(self):
        return tuple(self.meta["input_shape"])

    @property
    def poly_batch(self):
        """True when exported batch-polymorphic (any leading dim)."""
        return bool(self.meta.get("poly_batch", False))

    def jit_cache_size(self):
        """Number of compiled programs behind this predictor — one per
        distinct input shape seen (one total for fixed-shape
        artifacts)."""
        try:
            return self._call._cache_size()
        except Exception:      # cache introspection is jax-internal
            return -1

    def predict(self, x):
        import jax.numpy as jnp
        out = self._call(jnp.asarray(x))
        return _np.asarray(out)

    __call__ = predict


def load_predictor(path_or_bytes, donate_input=False):
    return Predictor(path_or_bytes, donate_input=donate_input)


# --------------------------------------------------- decoder artifacts --
#
# Autoregressive serving cannot ship a single fixed forward the way the
# predictor artifact does: the LLM engine needs the model in DECODE
# shape — the packed paged step, decode_flat (serving/llm/model.py) —
# with the paged-KV geometry riding along. The
# artifact therefore serializes the decoder CONFIG + parameter pytree
# (npz, CRC-free: the loader rebuilds the jitted programs, which the
# server warmup then pre-compiles per bucket); the loaded pair plugs
# straight into serving.llm.LLMServer.


def flatten_params(tree, prefix=""):
    """Flatten a param pytree (nested dict/list/tuple of arrays) to a
    flat ``{dot.joined.path: ndarray}`` dict — the shape decoder
    artifacts serialize and sharded checkpoints
    (``resilience.checkpoint.write_checkpoint``) store. Invert with
    :func:`unflatten_params`; ``serving.fleet`` publish builders use
    the pair to hot-swap LLM weights through checkpoint manifests."""
    out = {}
    if isinstance(tree, (dict, list, tuple)) and not tree:
        # an empty container flattens to nothing and would silently
        # vanish from the round-tripped tree — fail at export instead
        # of KeyError-ing at the loaded model's first forward
        raise ValueError(
            f"empty subtree at {prefix[:-1] or '<root>'!r} cannot "
            "round-trip through a decoder artifact")
    if isinstance(tree, dict):
        for k, v in tree.items():
            # the loader rebuilds the tree from dot-joined paths and
            # treats all-digit segments as LIST indices — a dict key
            # that is all digits or contains the separator would
            # silently corrupt the round-tripped structure, so refuse
            # it at export time with a clear error instead
            k = str(k)
            if "." in k or k.isdigit() or not k:
                raise ValueError(
                    f"unsupported param key {prefix + k!r}: decoder "
                    "artifact keys must be non-empty, non-numeric and "
                    "'.'-free (list positions serialize as digits)")
            out.update(flatten_params(v, f"{prefix}{k}."))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(flatten_params(v, f"{prefix}{i}."))
    else:
        out[prefix[:-1]] = _np.asarray(tree)
    return out


def unflatten_params(flat):
    """Inverse of :func:`flatten_params`: rebuild the param pytree
    from a flat ``{dot.joined.path: ndarray}`` dict. All-digit path
    segments become LIST indices (the convention flatten enforces by
    refusing digit dict keys), everything else dict keys."""
    params = {}
    for key, arr in flat.items():
        parts = str(key).split(".")
        node = params
        for i, p in enumerate(parts[:-1]):
            nxt_is_idx = parts[i + 1].isdigit()
            if p.isdigit():
                p = int(p)
                while len(node) <= p:
                    node.append({} if not nxt_is_idx else [])
                node = node[p]
            else:
                if p not in node:
                    node[p] = [] if nxt_is_idx else {}
                node = node[p]
        leaf = parts[-1]
        if leaf.isdigit():
            li = int(leaf)
            while len(node) <= li:
                node.append(None)
            node[li] = arr
        else:
            node[leaf] = arr
    return params


# scale arrays ride in the same npz under a reserved prefix; the
# prefix contains "." so flatten_params can never produce a colliding
# weight path (it refuses dotted dict keys)
_SCALE_PREFIX = "scale."


def export_decoder(model, params, path=None):
    """Serialize a paged-decode model (a ``serving.llm.TinyDecoder``-
    shaped object: ``.config`` + param pytree) into a self-contained
    decode-serving artifact. Returns the bytes; writes ``path`` if
    given. Load with :func:`load_decoder`, serve with
    ``serving.llm.LLMServer``.

    ``params`` may be a ``serving.llm.QuantizedWeights`` (ISSUE 20):
    the int8/fp8 leaves serialize as-is (npz stores fp8-e4m3 natively),
    the per-channel scale dict rides under ``scale.``-prefixed npz
    keys, and the header records ``weight_dtype`` / ``weight_calib``
    so :func:`load_decoder` rebuilds the QuantizedWeights — letting
    ``serving.fleet.FleetRouter.publish`` hot-swap an fp32 model to
    its quantized twin through the same artifact path."""
    import io
    meta = {
        "format": "mxtpu-llm-decoder/npz",
        "config": model.config.to_dict(),
    }
    qw = None
    if hasattr(params, "scales") and hasattr(params, "params") \
            and hasattr(params, "dtype"):      # QuantizedWeights
        qw = params
        params = qw.params
    flat = flatten_params(params)
    if qw is not None:
        meta["weight_dtype"] = qw.dtype
        meta["weight_calib"] = qw.method
        if getattr(qw, "methods", None):
            meta["weight_methods"] = dict(qw.methods)
        meta["scales"] = sorted(qw.scales)
        for k, v in qw.scales.items():
            flat[_SCALE_PREFIX + k] = _np.asarray(v)
    buf = io.BytesIO()
    _np.savez(buf, **flat)
    blob = buf.getvalue()
    meta["arrays"] = sorted(flat)
    header = json.dumps(meta).encode()
    artifact = _LLM_MAGIC + struct.pack("<I", len(header)) \
        + header + blob
    if path:
        with open(path, "wb") as f:
            f.write(artifact)
    return artifact


def params_from_arrays(flat):
    """Rebuild decoder params from a flat ``{path: ndarray}`` dict —
    the shape ``FleetRouter.publish`` hands to builders. Plain trees
    come back via :func:`unflatten_params`; when ``scale.``-prefixed
    entries are present (a quantized weight set, ISSUE 20) the result
    is a ``serving.llm.QuantizedWeights`` instead, so one fleet
    builder serves fp32 and quantized publishes alike::

        builder = lambda arrays: LLMServer(
            model, mx.deploy.params_from_arrays(arrays))
    """
    scales = {k[len(_SCALE_PREFIX):]: _np.asarray(v)
              for k, v in flat.items() if k.startswith(_SCALE_PREFIX)}
    if not scales:
        return unflatten_params(flat)
    from .serving.llm.quant import QuantizedWeights
    weights = {k: _np.asarray(v) for k, v in flat.items()
               if not k.startswith(_SCALE_PREFIX)}
    qleaf = weights[next(iter(sorted(scales)))]
    return QuantizedWeights(unflatten_params(weights), scales,
                            qleaf.dtype.name)


def load_decoder(path_or_bytes):
    """Load an :func:`export_decoder` artifact. Returns
    ``(model, params)`` ready for ``serving.llm.LLMServer(model,
    params)`` / ``LLMEngine``."""
    import io
    from .serving.llm.model import DecoderConfig, TinyDecoder
    artifact = path_or_bytes
    if isinstance(artifact, str):
        with open(artifact, "rb") as f:
            artifact = f.read()
    if not artifact.startswith(_LLM_MAGIC):
        raise ValueError("not an mxnet_tpu decoder artifact")
    off = len(_LLM_MAGIC)
    (hlen,) = struct.unpack_from("<I", artifact, off)
    off += 4
    meta = json.loads(artifact[off:off + hlen].decode())
    if meta.get("format") != "mxtpu-llm-decoder/npz":
        raise ValueError(f"unknown decoder format {meta.get('format')!r}")
    flat = dict(_np.load(io.BytesIO(artifact[off + hlen:])))
    missing = set(meta.get("arrays", [])) - set(flat)
    if missing:
        raise ValueError(f"decoder artifact missing arrays: "
                         f"{sorted(missing)[:4]}")
    model = TinyDecoder(DecoderConfig.from_dict(meta["config"]))
    if meta.get("weight_dtype"):
        from .serving.llm.quant import QuantizedWeights
        scales = {k[len(_SCALE_PREFIX):]: v for k, v in flat.items()
                  if k.startswith(_SCALE_PREFIX)}
        weights = {k: v for k, v in flat.items()
                   if not k.startswith(_SCALE_PREFIX)}
        # npz stores fp8-e4m3 bytes faithfully but reads them back as
        # raw void ("|V1") — the descr cannot name the extended dtype.
        # The scale list identifies exactly the quantized leaves, so
        # view-cast those back to the header dtype.
        wdt = _np.dtype(meta["weight_dtype"])
        for k in scales:
            if k in weights and weights[k].dtype != wdt:
                weights[k] = weights[k].view(wdt)
        return model, QuantizedWeights(
            unflatten_params(weights), scales, meta["weight_dtype"],
            method=meta.get("weight_calib", "absmax"),
            methods=meta.get("weight_methods"))
    return model, unflatten_params(flat)
