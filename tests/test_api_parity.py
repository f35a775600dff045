"""API-surface parity: the reference's python/paddle __all__ exports must
all resolve here (top-level, nn, nn.functional), plus numeric checks for the
round-2 long-tail additions (reference: python/paddle/tensor/math.py,
manipulation.py, nn/functional/loss.py et al.)."""
import ast
import os

import numpy as np
import pytest
import torch

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

REF = "/root/reference/python/paddle"
# the reference's tree lives outside the repo; the numeric checks need none
needs_ref = pytest.mark.skipif(
    not os.path.isdir(REF), reason=f"the reference's tree is not at {REF}")

rng = np.random.default_rng(0)


def _np(t):
    return np.asarray(t._value)


def _ref_all(path):
    tree = ast.parse(open(path).read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            tgt = (node.targets[0] if isinstance(node, ast.Assign)
                   else node.target)
            if isinstance(tgt, ast.Name) and tgt.id == "__all__" and \
                    isinstance(node.value, ast.List):
                names += [ast.literal_eval(e) for e in node.value.elts]
    return set(names)


_MODULES = [
    "", "nn", "nn.functional", "linalg", "fft", "signal", "sparse", "amp",
    "io", "optimizer", "metric", "autograd", "jit", "static", "vision",
    "distribution", "audio", "text", "geometric", "incubate",
    "quantization", "device", "utils", "distributed",
    # deep namespaces (SURVEY §2.5 package inventory)
    "vision.transforms", "vision.ops", "vision.models", "vision.datasets",
    "incubate.nn.functional", "distributed.fleet", "nn.initializer",
    "nn.utils", "amp.debugging", "incubate.autograd", "optimizer.lr",
    "inference", "callbacks", "regularizer", "hub", "onnx", "sysconfig",
    "nn.quant", "distributed.passes", "distributed.rpc", "incubate.nn",
    "distributed.fleet.utils", "incubate.optimizer",
    "sparse.nn", "sparse.nn.functional", "incubate.optimizer.functional",
    "incubate.asp", "quantization.quanters", "quantization.observers",
    "profiler", "distributed.sharding", "device.xpu", "device.cuda",
    "cost_model", "distributed.communication",
    "distributed.communication.stream", "static.nn", "audio.backends",
    "audio.datasets", "audio.features", "audio.functional",
]


@needs_ref
@pytest.mark.parametrize("modname", _MODULES)
def test_all_exports_resolve(modname):
    path = (f"{REF}/{modname.replace('.', '/')}/__init__.py" if modname
            else f"{REF}/__init__.py")
    if modname and not os.path.exists(path):
        # flat modules (linalg.py, amp/debugging.py)
        path = f"{REF}/{modname.replace('.', '/')}.py"
    here = paddle
    for part in (modname.split(".") if modname else []):
        here = getattr(here, part)
    missing = sorted(n for n in _ref_all(path) if not hasattr(here, n))
    assert missing == [], f"{modname}: missing {len(missing)}: {missing}"


@needs_ref
def test_tensor_method_surface():
    """Every name in the reference's tensor_method_func list
    (tensor/__init__.py) resolves as a Tensor method here."""
    tree = ast.parse(open(f"{REF}/tensor/__init__.py").read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "tensor_method_func":
                    for e in ast.walk(node.value):
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, str):
                            names.append(e.value)
    assert len(names) > 300
    t = paddle.to_tensor(np.ones((2, 2), np.float32))
    missing = sorted(n for n in set(names) if not hasattr(t, n))
    assert missing == [], f"missing {len(missing)}: {missing}"

    # behavior spot-checks for the attach machinery
    x = paddle.to_tensor(np.array([[1.0, 2.0], [3.0, 4.0]], np.float32))
    assert x.take(paddle.to_tensor(np.array([0, 3], np.int32))
                  ).numpy().tolist() == [1.0, 4.0]
    assert x.kron(x).shape == [4, 4]
    assert x.inverse().shape == [2, 2]
    n1 = paddle.to_tensor(np.zeros((1000,), np.float32))
    n1.normal_(5.0, 0.1)
    assert abs(float(n1.numpy().mean()) - 5) < 0.05
    r = paddle.to_tensor(np.arange(4, dtype=np.float32))
    r.resize_([6])
    assert r.numpy().tolist() == [0.0, 1.0, 2.0, 3.0, 0.0, 1.0]
    s = paddle.to_tensor(np.zeros((2,), np.float32))
    s.set_(paddle.to_tensor(np.array([7.0, 8.0], np.float32)))
    assert s.numpy().tolist() == [7.0, 8.0]


def test_parallelize_plan():
    """Mirror of the reference parallelize workflow
    (auto_parallel/intermediate/parallelize.py) on the CPU mesh."""
    import paddle_tpu.distributed as dist

    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4),
                            dim_names=["dp", "mp"])
    dist.auto_parallel.set_mesh(mesh)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(16, 32)
            self.fc2 = nn.Linear(32, 16)

        def forward(self, x):
            return self.fc2(F.relu(self.fc1(x)))

    m = MLP()
    opt = paddle.optimizer.AdamW(parameters=m.parameters())
    m, opt = dist.parallelize(m, opt, mesh=mesh, config={
        "mp_config": {"parallelize_plan": {
            "fc1": dist.ColWiseParallel(),
            "fc2": dist.RowWiseParallel(),
        }},
        "dp_config": {"sharding_level": 1},
    })
    assert "mp" in str(m.fc1.weight._value.sharding.spec)
    x = paddle.to_tensor(rng.normal(size=(8, 16)).astype(np.float32))
    loss = paddle.mean(m(x))
    loss.backward()
    opt.step()
    assert np.isfinite(float(_np(loss)))

    st = dist.Strategy({"pipeline": {"enable": True,
                                     "schedule_mode": "1F1B"}})
    assert st.pipeline.schedule_mode == "1F1B" and not st.amp.enable

    # dist.split is the megatron parallel-layer helper
    # (reference collective.py split)
    xt = paddle.to_tensor(rng.normal(size=(4, 16)).astype(np.float32))
    out = dist.split(xt, (16, 32), "linear", axis=1)
    assert _np(out).shape == (4, 32)
    with pytest.raises(ValueError):
        dist.split(xt, (16, 32), "conv")


def test_compat_ops_numeric():
    x = paddle.arange(12).reshape([3, 4])
    np.testing.assert_array_equal(
        _np(paddle.take(x, paddle.to_tensor(np.array([[4, 5], [11, -1]],
                                                     np.int32)))),
        [[4, 5], [11, 11]])
    with pytest.raises(IndexError):
        paddle.take(x, paddle.to_tensor(np.array([12], np.int32)))
    np.testing.assert_array_equal(
        _np(paddle.take(x, paddle.to_tensor(np.array([12, 13], np.int32)),
                        mode="wrap")), [0, 1])
    np.testing.assert_array_equal(
        _np(paddle.isin(paddle.to_tensor(np.array([1, 2, 3], np.int32)),
                        paddle.to_tensor(np.array([2], np.int32)),
                        invert=True)), [True, False, True])
    np.testing.assert_array_equal(
        _np(paddle.combinations(paddle.to_tensor(
            np.array([1, 2, 3], np.int32)), with_replacement=True)),
        [[1, 1], [1, 2], [1, 3], [2, 2], [2, 3], [3, 3]])
    bd = paddle.block_diag([paddle.to_tensor(np.ones((2, 2), np.float32)),
                            paddle.to_tensor(np.ones((1, 3), np.float32))])
    assert _np(bd).shape == (3, 5) and _np(bd).sum() == 7

    # scatter family vs torch
    a = rng.normal(size=(3, 4)).astype(np.float32)
    vals = rng.normal(size=(4,)).astype(np.float32)
    got = paddle.select_scatter(paddle.to_tensor(a), paddle.to_tensor(vals),
                                0, 1)
    ref = torch.select_scatter(torch.tensor(a), torch.tensor(vals), 0, 1)
    np.testing.assert_allclose(_np(got), ref.numpy())
    dg = rng.normal(size=(3,)).astype(np.float32)
    got = paddle.diagonal_scatter(paddle.to_tensor(a), paddle.to_tensor(dg))
    ref = torch.diagonal_scatter(torch.tensor(a), torch.tensor(dg))
    np.testing.assert_allclose(_np(got), ref.numpy())
    sv = rng.normal(size=(3, 2)).astype(np.float32)
    got = paddle.slice_scatter(paddle.to_tensor(a), paddle.to_tensor(sv),
                               [1], [0], [4], [2])
    ref = torch.slice_scatter(torch.tensor(a), torch.tensor(sv), 1, 0, 4, 2)
    np.testing.assert_allclose(_np(got), ref.numpy())

    got = paddle.vecdot(paddle.to_tensor(a), paddle.to_tensor(a))
    np.testing.assert_allclose(_np(got), (a * a).sum(-1), rtol=1e-5)
    np.testing.assert_array_equal(
        _np(paddle.unflatten(paddle.arange(12), 0, [3, -1])).shape, (3, 4))

    # incomplete gamma vs scipy
    from scipy.special import gammainc as sp_ginc
    av = np.array([0.5, 2.0, 5.0], np.float32)
    bv = np.array([1.0, 2.0, 3.0], np.float32)
    np.testing.assert_allclose(
        _np(paddle.gammainc(paddle.to_tensor(av), paddle.to_tensor(bv))),
        sp_ginc(av, bv), rtol=1e-5)

    # inplace variants adopt into the same Tensor
    t = paddle.to_tensor(np.array([1.0, 4.0], np.float32))
    paddle.sqrt_(t)
    np.testing.assert_allclose(_np(t), [1.0, 2.0])
    assert paddle.sgn(paddle.to_tensor(
        np.array([-3.0, 0.0], np.float32))).numpy().tolist() == [-1.0, 0.0]


def test_histogram_and_random_fills():
    edges = paddle.histogram_bin_edges(paddle.to_tensor(
        np.array([1, 2, 1], np.int32)), bins=4, min=0, max=3)
    np.testing.assert_allclose(_np(edges), [0, 0.75, 1.5, 2.25, 3.0])
    h, el = paddle.histogramdd(paddle.to_tensor(
        rng.normal(size=(100, 2)).astype(np.float32)), bins=5)
    assert _np(h).shape == (5, 5) and len(el) == 2
    assert float(_np(h).sum()) == 100

    # reference geometric_ fills continuous log(u)/log1p(-p) values
    # (tensor/creation.py:3247); mean = 1/ln(1/(1-p)) ≈ 1.443 for p=0.5
    g = paddle.to_tensor(np.zeros((500,), np.float32))
    g.geometric_(0.5)
    assert _np(g).min() > 0 and abs(_np(g).mean() - 1.443) < 0.4
    assert (_np(g) % 1 != 0).any()  # continuous, not floored
    sg = paddle.standard_gamma(paddle.to_tensor(
        np.full((500,), 4.0, np.float32)))
    assert abs(float(_np(sg).mean()) - 4.0) < 0.5


def test_finfo_iinfo_and_infra():
    fi = paddle.finfo(paddle.bfloat16)
    assert fi.bits == 16 and fi.eps == 0.0078125
    assert paddle.iinfo(paddle.int8).max == 127
    with pytest.raises(RuntimeError):
        paddle.CUDAPlace(0)
    p = paddle.create_parameter([4, 4], "float32")
    assert not p.stop_gradient and p.shape == [4, 4]
    assert paddle.flops(nn.Sequential(nn.Linear(8, 16)), [1, 8]) == 16 * 8
    info = paddle.summary(nn.Linear(8, 16), (1, 8))
    assert info["total_params"] == 8 * 16 + 16


def test_new_layers_match_torch():
    x = rng.normal(size=(2, 3, 7, 9, 11)).astype(np.float32)
    got = nn.AdaptiveAvgPool3D((2, 3, 4))(paddle.to_tensor(x))
    ref = torch.nn.AdaptiveAvgPool3d((2, 3, 4))(torch.tensor(x))
    np.testing.assert_allclose(_np(got), ref.numpy(), rtol=1e-5, atol=1e-6)

    x1 = rng.normal(size=(2, 3, 13)).astype(np.float32)
    got = nn.AdaptiveMaxPool1D(5)(paddle.to_tensor(x1))
    ref = torch.nn.AdaptiveMaxPool1d(5)(torch.tensor(x1))
    np.testing.assert_allclose(_np(got), ref.numpy(), rtol=1e-5)

    got = nn.LPPool1D(2.0, 3, stride=2)(paddle.to_tensor(x1))
    ref = torch.nn.LPPool1d(2.0, 3, stride=2)(torch.tensor(x1))
    np.testing.assert_allclose(_np(got), ref.numpy(), rtol=1e-4, atol=1e-5)

    inp = rng.normal(size=(5, 7)).astype(np.float32)
    lbl = rng.integers(0, 7, 5)
    got = nn.MultiMarginLoss()(paddle.to_tensor(inp),
                               paddle.to_tensor(lbl.astype(np.int32)))
    ref = torch.nn.MultiMarginLoss()(torch.tensor(inp), torch.tensor(lbl))
    np.testing.assert_allclose(float(_np(got)), float(ref), rtol=1e-5)

    y2 = (rng.integers(0, 2, (5, 7)) * 2 - 1).astype(np.float32)
    got = nn.SoftMarginLoss()(paddle.to_tensor(inp), paddle.to_tensor(y2))
    ref = torch.nn.SoftMarginLoss()(torch.tensor(inp), torch.tensor(y2))
    np.testing.assert_allclose(float(_np(got)), float(ref), rtol=1e-5)

    a = rng.normal(size=(4, 6)).astype(np.float32)
    b = rng.normal(size=(4, 6)).astype(np.float32)
    got = nn.PairwiseDistance()(paddle.to_tensor(a), paddle.to_tensor(b))
    ref = torch.nn.PairwiseDistance()(torch.tensor(a), torch.tensor(b))
    np.testing.assert_allclose(_np(got), ref.numpy(), rtol=1e-5)

    xs = rng.normal(size=(2, 3, 4, 4)).astype(np.float32)
    got = nn.Softmax2D()(paddle.to_tensor(xs))
    ref = torch.nn.Softmax2d()(torch.tensor(xs))
    np.testing.assert_allclose(_np(got), ref.numpy(), rtol=1e-5)

    got = nn.Unflatten(1, [1, 3])(paddle.to_tensor(xs))
    assert _np(got).shape == (2, 1, 3, 4, 4)
    got = nn.ZeroPad1D([1, 2])(paddle.to_tensor(x1))
    assert _np(got).shape == (2, 3, 16)
    got = nn.ZeroPad3D(1)(paddle.to_tensor(x))
    assert _np(got).shape == (2, 3, 9, 11, 13)


def test_rnnt_loss_vs_dp():
    from scipy.special import log_softmax, logsumexp

    def ref_rnnt(acts, labels, il, ll, blank=0):
        B = acts.shape[0]
        out = []
        for b in range(B):
            Tb, Ub = il[b], ll[b]
            lp = log_softmax(acts[b].astype(np.float64), axis=-1)
            alpha = np.full((Tb, Ub + 1), -np.inf)
            alpha[0, 0] = 0.0
            for t in range(Tb):
                for u in range(Ub + 1):
                    if t == 0 and u == 0:
                        continue
                    cands = []
                    if t > 0:
                        cands.append(alpha[t - 1, u] + lp[t - 1, u, blank])
                    if u > 0:
                        cands.append(alpha[t, u - 1]
                                     + lp[t, u - 1, labels[b, u - 1]])
                    alpha[t, u] = logsumexp(cands)
            out.append(-(alpha[Tb - 1, Ub] + lp[Tb - 1, Ub, blank]))
        return np.array(out)

    logits = rng.normal(size=(3, 7, 5, 6)).astype(np.float32)
    targets = rng.integers(1, 6, (3, 4)).astype(np.int32)
    il = np.array([7, 5, 6], np.int32)
    ll = np.array([4, 2, 3], np.int32)
    got = F.rnnt_loss(paddle.to_tensor(logits), paddle.to_tensor(targets),
                      paddle.to_tensor(il), paddle.to_tensor(ll),
                      fastemit_lambda=0.0, reduction="none")
    np.testing.assert_allclose(_np(got), ref_rnnt(logits, targets, il, ll),
                               rtol=1e-4)
    lay = nn.RNNTLoss()
    out = lay(paddle.to_tensor(logits), paddle.to_tensor(targets),
              paddle.to_tensor(il), paddle.to_tensor(ll))
    assert np.isfinite(float(_np(out)))


def test_attention_variants():
    B, S, H, D = 2, 8, 2, 16
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    k = rng.normal(size=(B, S, H, D)).astype(np.float32)
    v = rng.normal(size=(B, S, H, D)).astype(np.float32)

    def dense(qv, kv, vv, mask):
        from scipy.special import softmax
        qt = np.einsum("bshd->bhsd", qv)
        kt = np.einsum("bshd->bhsd", kv)
        vt = np.einsum("bshd->bhsd", vv)
        sc = np.einsum("bhsd,bhtd->bhst", qt, kt) / np.sqrt(D)
        sc = np.where(mask, sc, -1e30)
        p = softmax(sc, axis=-1)
        return np.einsum("bhst,bhtd->bshd", p, vt).astype(np.float32)

    causal = np.tril(np.ones((S, S), bool))[None, None]
    sri = np.full((B, 1, S, 1), S, np.int32)
    sri[0, 0, 2, 0] = 5
    got = F.flashmask_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                paddle.to_tensor(v), paddle.to_tensor(sri),
                                causal=True)
    mask = np.broadcast_to(causal, (B, H, S, S)).copy()
    mask[0, :, 5:, 2] = False
    np.testing.assert_allclose(_np(got), dense(q, k, v, mask), rtol=2e-3,
                               atol=2e-4)

    qkv = np.stack([q, k, v], axis=2)
    got, _ = F.flash_attn_qkvpacked(paddle.to_tensor(qkv), causal=True)
    np.testing.assert_allclose(_np(got), dense(q, k, v, causal), rtol=2e-3,
                               atol=2e-4)

    qh = np.einsum("bshd->bhsd", q)
    kh = np.einsum("bshd->bhsd", k)
    vh = np.einsum("bshd->bhsd", v)
    off = np.tile(np.arange(0, S * S + 1, S, dtype=np.int32), (B, H, 1))
    colsarr = np.tile(np.tile(np.arange(S, dtype=np.int32), S), (B, H, 1))
    got = F.sparse_attention(paddle.to_tensor(qh), paddle.to_tensor(kh),
                             paddle.to_tensor(vh), paddle.to_tensor(off),
                             paddle.to_tensor(colsarr))
    want = np.einsum("bshd->bhsd", dense(q, k, v, np.ones((1, 1, S, S),
                                                          bool)))
    np.testing.assert_allclose(_np(got), want, rtol=2e-3, atol=2e-4)


def test_hsigmoid_and_beam_search():
    xin = rng.normal(size=(3, 5)).astype(np.float32)
    hs = nn.HSigmoidLoss(5, 8)
    out = hs(paddle.to_tensor(xin),
             paddle.to_tensor(np.array([[0], [3], [7]], np.int64)))
    assert _np(out).shape == (3, 1) and np.isfinite(_np(out)).all()

    V = 5

    class ToyCell:
        def __call__(self, inp, state):
            tok = _np(inp).astype(np.int64)
            logits = np.full((tok.shape[0], V), -5.0, np.float32)
            for i, t in enumerate(tok):
                logits[i, (t + 1) % V] = 5.0
            return paddle.to_tensor(logits), state

    dec = nn.BeamSearchDecoder(ToyCell(), start_token=1, end_token=4,
                               beam_size=2)
    ids, scores = nn.dynamic_decode(dec, inits=None, max_step_num=6,
                                    batch_size=2)
    assert _np(ids)[0, 0].tolist()[:3] == [2, 3, 4]
