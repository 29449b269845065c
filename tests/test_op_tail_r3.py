"""Round-3 op tail: transformer interleaved matmuls, image ops, npx/npi
internals, packed-triangular linalg, scatter family, optimizer multi
variants, quantized op family, SyncBatchNorm, Correlation.

Each test pins a numpy/jax oracle for the reference semantics cited in
the op docstrings (src/operator/contrib/transformer.cc, image/,
optimizer_op.cc, quantization/, correlation.cc, ...).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.registry import _REGISTRY


def _op(name, *args, **kw):
    import jax.numpy as jnp
    arrays = [jnp.asarray(a) for a in args]
    op = _REGISTRY[name]
    if op.variadic:
        return op.impl(arrays, **kw)
    return op.impl(*arrays, **kw)


def test_interleaved_matmul_selfatt_roundtrip():
    rng = np.random.RandomState(0)
    T, B, H, D = 5, 2, 3, 4
    qkv = rng.randn(T, B, 3 * H * D).astype(np.float32)
    att = _op("_contrib_interleaved_matmul_selfatt_qk", qkv, heads=H)
    assert att.shape == (B * H, T, T)
    # oracle straight from the reference docstring
    tmp = qkv.reshape(T, B, H, 3, D)
    q = tmp[:, :, :, 0, :].transpose(1, 2, 0, 3).reshape(B * H, T, D)
    k = tmp[:, :, :, 1, :].transpose(1, 2, 0, 3).reshape(B * H, T, D)
    want = (q / np.sqrt(D)) @ k.transpose(0, 2, 1)
    np.testing.assert_allclose(np.asarray(att), want, rtol=1e-5,
                               atol=1e-5)
    out = _op("_contrib_interleaved_matmul_selfatt_valatt", qkv,
              np.asarray(att), heads=H)
    v = tmp[:, :, :, 2, :].transpose(1, 2, 0, 3).reshape(B * H, T, D)
    want_out = (np.asarray(att) @ v).reshape(B, H, T, D)\
        .transpose(2, 0, 1, 3).reshape(T, B, H * D)
    np.testing.assert_allclose(np.asarray(out), want_out, rtol=1e-5,
                               atol=1e-5)


def test_interleaved_matmul_encdec():
    rng = np.random.RandomState(1)
    Tq, Tk, B, H, D = 4, 6, 2, 2, 3
    q = rng.randn(Tq, B, H * D).astype(np.float32)
    kv = rng.randn(Tk, B, 2 * H * D).astype(np.float32)
    att = _op("_contrib_interleaved_matmul_encdec_qk", q, kv, heads=H)
    assert att.shape == (B * H, Tq, Tk)
    out = _op("_contrib_interleaved_matmul_encdec_valatt", kv,
              np.asarray(att), heads=H)
    assert out.shape == (Tq, B, H * D)
    assert np.isfinite(np.asarray(out)).all()


def test_image_ops():
    rng = np.random.RandomState(2)
    img = (rng.rand(8, 10, 3) * 255).astype(np.uint8)
    crop = _op("_image_crop", img, x=2, y=1, width=5, height=4)
    np.testing.assert_array_equal(np.asarray(crop), img[1:5, 2:7])
    t = _op("_image_to_tensor", img)
    assert t.shape == (3, 8, 10)
    np.testing.assert_allclose(np.asarray(t)[0], img[:, :, 0] / 255.0,
                               rtol=1e-6)
    norm = _op("_image_normalize", np.asarray(t),
               mean=(0.5, 0.5, 0.5), std=(0.25, 0.25, 0.25))
    np.testing.assert_allclose(np.asarray(norm),
                               (np.asarray(t) - 0.5) / 0.25, rtol=1e-5)
    r = _op("_image_resize", img, size=(5, 4))
    assert r.shape == (4, 5, 3)


def test_npx_reshape_codes():
    x = np.zeros((2, 3, 4, 5), np.float32)
    assert _op("_npx_reshape", x, newshape=(0, -1)).shape == (2, 60)
    assert _op("_npx_reshape", x, newshape=(0, -2)).shape == \
        (2, 3, 4, 5)
    assert _op("_npx_reshape", x, newshape=(0, 0, -1)).shape == \
        (2, 3, 20)


def test_scatter_family():
    data = np.array([1.0, 0.0, -2.0, 0.0], np.float32)
    out = _op("_scatter_minus_scalar", data, scalar=1.0)
    np.testing.assert_allclose(np.asarray(out), [0, 0, -3, 0])
    lhs = np.zeros((3, 3), np.float32)
    idx = np.array([[0, 2], [1, 0]])
    out = _op("_scatter_set_nd", lhs, np.array([5.0, 7.0], np.float32),
              idx)
    assert np.asarray(out)[0, 1] == 5 and np.asarray(out)[2, 0] == 7


def test_preloaded_multi_sgd_matches_single():
    rng = np.random.RandomState(3)
    w1, g1 = rng.randn(4), rng.randn(4)
    w2, g2 = rng.randn(3), rng.randn(3)
    lrs = np.array([0.1, 0.2], np.float32)
    wds = np.array([0.0, 0.01], np.float32)
    outs = _op("preloaded_multi_sgd_update",
               w1.astype(np.float32), g1.astype(np.float32),
               w2.astype(np.float32), g2.astype(np.float32), lrs, wds)
    want1 = w1 - 0.1 * g1
    want2 = w2 - 0.2 * (g2 + 0.01 * w2)
    np.testing.assert_allclose(np.asarray(outs[0]), want1, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1]), want2, rtol=1e-5)


def test_multi_adamw_update():
    rng = np.random.RandomState(4)
    w = rng.randn(5).astype(np.float32)
    g = rng.randn(5).astype(np.float32)
    m = np.zeros(5, np.float32)
    v = np.zeros(5, np.float32)
    rescale = np.asarray(1.0, np.float32)
    outs = _op("_multi_adamw_update", w, g, m, v, rescale,
               lrs=(0.01,), wds=(0.0,), etas=(1.0,))
    m_want = 0.1 * g
    v_want = 0.001 * g * g
    w_want = w - 1.0 * (0.01 * m_want / (np.sqrt(v_want) + 1e-8))
    np.testing.assert_allclose(np.asarray(outs[0]), w_want, rtol=1e-5)


def test_sparse_and_group_adagrad():
    w = np.ones((3, 2), np.float32)
    g = np.zeros((3, 2), np.float32)
    g[1] = [1.0, -2.0]                       # only row 1 has gradient
    h = np.zeros((3, 2), np.float32)
    new_w, new_h = _op("_sparse_adagrad_update", w, g, h, lr=0.1)
    assert np.allclose(np.asarray(new_w)[0], 1.0)    # untouched rows
    assert np.allclose(np.asarray(new_w)[2], 1.0)
    assert not np.allclose(np.asarray(new_w)[1], 1.0)
    hg = np.zeros((3,), np.float32)
    new_w2, new_hg = _op("_contrib_group_adagrad_update", w, g, hg,
                         lr=0.1)
    assert np.asarray(new_hg)[1] > 0 and np.asarray(new_hg)[0] == 0


def test_quantized_family():
    import jax.numpy as jnp
    q = np.array([-50, -1, 0, 30, 127], np.int8)
    out, mn, mx_ = _op("_contrib_quantized_act", q, -1.0, 1.0)
    np.testing.assert_array_equal(np.asarray(out), [0, 0, 0, 30, 127])
    assert float(mn) == 0.0
    # elemwise add requantizes: dequant oracle
    a = np.array([127, -127, 64], np.int8)
    b = np.array([127, 127, 0], np.int8)
    s, mn, mx_ = _op("_contrib_quantized_elemwise_add",
                     a, b, -1.0, 1.0, -2.0, 2.0)
    real = a / 127.0 * 1.0 + b / 127.0 * 2.0
    back = np.asarray(s, np.float32) * (float(mx_) / 127.0)
    np.testing.assert_allclose(back, real, atol=float(mx_) / 127.0)
    # concat to widest range
    c, mn, mx_ = _op("_contrib_quantized_concat",
                     np.array([[127]], np.int8),
                     np.array([[127]], np.int8),
                     np.asarray(-1.0), np.asarray(-4.0),
                     np.asarray(1.0), np.asarray(4.0),
                     num_args=2, dim=1)
    assert float(mx_) == 4.0
    np.testing.assert_array_equal(np.asarray(c), [[32, 127]])


def test_calibrate_entropy_op():
    rng = np.random.RandomState(5)
    data = np.concatenate([rng.randn(100000) * 0.5, [60.0]])
    hist, edges = np.histogram(data, bins=4001, range=(-64, 64))
    mn, mx_ = _op("_contrib_calibrate_entropy", hist.astype(np.float32),
                  edges.astype(np.float32))
    assert 0.5 < float(mx_) < 30.0
    assert float(mn) == -float(mx_)


def test_sync_batch_norm_pmean():
    """Stats must be identical to a BatchNorm over the CONCATENATED
    per-device batches (reference sync_batch_norm.cc contract)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    rng = np.random.RandomState(6)
    x = rng.randn(8, 4, 6).astype(np.float32)     # (N, C, W), dp over N
    gamma = np.ones(4, np.float32)
    beta = np.zeros(4, np.float32)
    mm = np.zeros(4, np.float32)
    mv = np.ones(4, np.float32)
    sync = _REGISTRY["_contrib_SyncBatchNorm"].impl

    def local(x):
        return sync(x, jnp.asarray(gamma), jnp.asarray(beta),
                    jnp.asarray(mm), jnp.asarray(mv), fix_gamma=False,
                    axis=1, axis_name="dp", _training=True)

    f = shard_map(local, mesh=mesh, in_specs=(P("dp"),),
                  out_specs=P("dp"))
    out = np.asarray(f(jnp.asarray(x)))
    # oracle: plain BatchNorm over the full batch
    ref = _REGISTRY["BatchNorm"].impl(
        jnp.asarray(x), jnp.asarray(gamma), jnp.asarray(beta),
        jnp.asarray(mm), jnp.asarray(mv), fix_gamma=False, axis=1,
        _training=True)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_correlation_oracle():
    rng = np.random.RandomState(7)
    n, c, h, w = 1, 3, 6, 6
    x1 = rng.randn(n, c, h, w).astype(np.float32)
    x2 = rng.randn(n, c, h, w).astype(np.float32)
    out = np.asarray(_op("Correlation", x1, x2, kernel_size=1,
                         max_displacement=1, stride1=1, stride2=1,
                         pad_size=1))
    assert out.shape == (1, 9, 8, 8)
    # center displacement (0,0) at interior position equals channel-mean
    # of the product
    want = (x1[0, :, 2, 3] * x2[0, :, 2, 3]).mean()
    np.testing.assert_allclose(out[0, 4, 3, 4], want, rtol=1e-5)


def test_count_sketch():
    data = np.array([[1.0, 2.0, 3.0]], np.float32)
    h = np.array([0, 1, 0])
    s = np.array([1.0, -1.0, 1.0], np.float32)
    out = _op("_contrib_count_sketch", data, h, s, out_dim=2)
    np.testing.assert_allclose(np.asarray(out), [[4.0, -2.0]])


def test_bipartite_matching():
    scores = np.array([[0.9, 0.1], [0.8, 0.7]], np.float32)
    rows, cols = _op("_contrib_bipartite_matching", scores,
                     threshold=0.05)
    np.testing.assert_array_equal(np.asarray(rows), [0, 1])
    np.testing.assert_array_equal(np.asarray(cols), [0, 1])


def test_trian_roundtrip():
    rng = np.random.RandomState(8)
    A = rng.randn(4, 4).astype(np.float32)
    packed = _op("_linalg_extracttrian", A, offset=0, lower=True)
    assert packed.shape == (10,)
    back = _op("_linalg_maketrian", np.asarray(packed), offset=0,
               lower=True)
    np.testing.assert_allclose(np.asarray(back), np.tril(A), rtol=1e-6)


def test_boolean_mask_and_getnnz():
    x = np.array([[1.0, 0.0], [0.0, 0.0], [3.0, 4.0]], np.float32)
    sel = _op("_contrib_boolean_mask", x, np.array([1, 0, 1]))
    np.testing.assert_allclose(np.asarray(sel), x[[0, 2]])
    assert int(_op("_contrib_getnnz", x)) == 3


def test_sparse_embedding_op_grad():
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray.sparse import RowSparseNDArray
    import mxnet_tpu.autograd as ag
    w = nd.array(np.random.RandomState(9).randn(20, 3)
                 .astype(np.float32))
    w.attach_grad()
    x = nd.array(np.array([1, 5]))
    with ag.record():
        out = nd._contrib_SparseEmbedding(x, w, input_dim=20,
                                          output_dim=3)
        loss = (out * out).sum()
    loss.backward()
    assert isinstance(w.grad, RowSparseNDArray)
