"""Compiled-mode (real TPU) tests for the r5 surfaces: sparse conv
gather paths and the ERNIE bench lane model."""
import numpy as np

import paddle_tpu as P
from paddle_tpu import sparse
import paddle_tpu.nn.functional as F
import paddle_tpu.sparse.nn as spnn


def _site_sparse(rng, shape, k):
    N, D, H, W, C = shape
    dense = np.zeros(shape, np.float32)
    sites = rng.choice(N * D * H * W, size=k, replace=False)
    n, z, y, x = np.unravel_index(sites, (N, D, H, W))
    dense[n, z, y, x] = rng.standard_normal((k, C))
    return dense


class TestSparseConvOnSilicon:
    def test_subm_gather_matches_dense(self):
        rng = np.random.default_rng(0)
        dense = _site_sparse(rng, (2, 8, 8, 8, 4), 60)
        xt = sparse.to_sparse_coo(P.to_tensor(dense), sparse_dim=4)
        P.seed(0)
        conv = spnn.SubmConv3D(4, 8, kernel_size=3, padding=1)
        out_g = conv(xt)
        out_d = conv.forward_dense(xt)
        np.testing.assert_allclose(np.asarray(out_g._value),
                                   np.asarray(out_d._value),
                                   rtol=1e-3, atol=1e-4)

    def test_strided_stack_trains(self):
        rng = np.random.default_rng(1)
        P.seed(0)
        c1 = spnn.Conv3D(3, 8, kernel_size=3, stride=2, padding=1)
        bn = spnn.BatchNorm(8)
        c2 = spnn.SubmConv3D(8, 4, kernel_size=3, padding=1)
        head = P.nn.Linear(4, 2)
        opt = P.optimizer.Adam(
            learning_rate=1e-2,
            parameters=c1.parameters() + bn.parameters()
            + c2.parameters() + head.parameters())
        losses = []
        for _ in range(4):
            opt.clear_grad()
            dense = _site_sparse(rng, (2, 10, 10, 10, 3), 60)
            xt = sparse.to_sparse_coo(P.to_tensor(dense), sparse_dim=4)
            h = c2(spnn.ReLU()(bn(c1(xt))))
            loss = ((head(h.values().mean(axis=0))
                     - P.to_tensor(np.array([1.0, -1.0],
                                            np.float32))) ** 2).sum()
            loss.backward()
            opt.step()
            losses.append(float(loss))
        assert np.isfinite(losses).all()


class TestErnieOnSilicon:
    def test_ernie_train_step_compiles(self):
        from paddle_tpu.models.ernie import ErnieForPretraining, ernie_tiny

        P.seed(0)
        cfg = ernie_tiny()
        model = ErnieForPretraining(cfg)
        opt = P.optimizer.AdamW(learning_rate=1e-4,
                                parameters=model.parameters())

        @P.jit.to_static
        def step(ids, task_ids, labels):
            opt.clear_grad()
            with P.amp.auto_cast(level="O1", dtype="bfloat16"):
                pred = model(ids, task_type_ids=task_ids)
            loss = F.cross_entropy(
                pred.reshape([-1, cfg.vocab_size]), labels.reshape([-1]))
            loss.backward()
            opt.step()
            return loss

        rng = np.random.default_rng(0)
        ids = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 64)),
                          dtype="int64")
        task = P.to_tensor(np.zeros((2, 64)), dtype="int64")
        labels = P.to_tensor(rng.integers(0, cfg.vocab_size, (2, 64)),
                             dtype="int64")
        l1 = float(step(ids, task, labels))
        l2 = float(step(ids, task, labels))
        assert np.isfinite([l1, l2]).all()
        assert l2 < l1 * 1.5
