"""A run with its timed path broken underneath comes out not correct: once
for each fault a cell can have (on one chip there is no exchange between
chips to leave out). The chip check is skipped; everything else is the
harness's own run, at the tiny deployment on the CPU."""
import numpy as np
import pytest

import conftest


def _unchanged_state(factory):
    def make(self):
        step = factory(self)

        def broken(params, opt_state, *batch, **kw):
            loss = step(params, opt_state, *batch, **kw)[2]
            return params, opt_state, loss

        return broken

    return make


@pytest.mark.parametrize("workload,factory", [
    ("lightgcn-ub.host", "_make_sparse_step"),
    ("gat-ub.fused", "_make_fused_step"),
    ("lightgcn-ub.fused", "_make_fused_step"),
])
def test_step_returns_state_unchanged(tiny_root, monkeypatch, workload,
                                      factory):
    from repro.train.trainer import Graph4RecTrainer

    monkeypatch.setattr(Graph4RecTrainer, factory, _unchanged_state(
        getattr(Graph4RecTrainer, factory)))
    out = conftest.run_cell(tiny_root, workload)
    assert out["correct"] is False
    assert out["checks"]["change_norm"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(tiny_root, monkeypatch):
    from repro.core import model as model_lib

    orig = model_lib.loss_fn

    def half(params, cfg, batch):
        b = dict(batch)
        for part in ("src", "dst"):
            levels, slots = batch[part]
            b[part] = ([l[: l.shape[0] // 2] for l in levels], slots)
        return orig(params, cfg, b)

    monkeypatch.setattr(model_lib, "loss_fn", half)
    out = conftest.run_cell(tiny_root, "lightgcn-ub.host")
    assert out["correct"] is False
    assert out["checks"]["loss"]["value"] > out["checks"]["loss"]["limit"]


def test_sampled_answer_altered(tiny_root, monkeypatch):
    from repro.sampling import pipeline

    orig = pipeline.sample_ego_batch

    def altered(rng, engine, centers, config):
        ego = orig(rng, engine, centers, config)
        # a node is never its own neighbour in a user-item graph
        ego.levels[1][0, 0] = ego.levels[0][0, 0]
        return ego

    monkeypatch.setattr(pipeline, "sample_ego_batch", altered)
    out = conftest.run_cell(tiny_root, "lightgcn-ub.host")
    assert out["correct"] is False
    assert out["checks"]["batch_invalid"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_retrieval_faults(tiny_root, monkeypatch, fault):
    import repro.retrieval as retrieval

    orig = retrieval.chunked_topk

    def broken(queries, items, k, exclude=None, **kw):
        s, i = orig(queries, items, k, exclude=exclude, **kw)
        i = i.copy()
        if fault == "answer_altered":
            i[:, 0] = (i[:, 0] + 7) % len(items)
        else:
            i[len(i) // 2:] = -1
        return s, i

    monkeypatch.setattr(retrieval, "chunked_topk", broken)
    out = conftest.run_cell(tiny_root, "lightgcn-ub.retrieve")
    assert out["correct"] is False
    assert np.isfinite(out["checks"]["score_gap"]["value"])
