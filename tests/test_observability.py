"""Observability tests: stats stream, storage, report, NaN debug mode,
profiler hook (review item 9 — one flag turns on a per-iteration jsonl
stream + trace dump)."""
import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.layers_core import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.ui import (FileStatsStorage, InMemoryStatsStorage,
                                   ProfilerListener, StatsListener,
                                   render_report)


def _model(lr=0.05, seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=lr)).list()
            .layer(DenseLayer(n_in=6, n_out=12, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=96):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    return ListDataSetIterator(DataSet(x, y).batch_by(32))


def test_stats_listener_jsonl_stream(tmp_path):
    path = str(tmp_path / "stats.jsonl")
    storage = FileStatsStorage(path)
    m = _model()
    m.set_listeners(StatsListener(storage, collect_param_stats=True,
                                  param_stats_frequency=4))
    m.fit(_data(), n_epochs=3)
    recs = storage.records()
    assert len(recs) == 9
    r = recs[1]
    assert {"iteration", "epoch", "loss", "timestamp",
            "batch_size"} <= set(r)
    assert "examples_per_sec" in r
    # param summaries every 4th iteration
    with_params = [r for r in recs if "params" in r]
    assert len(with_params) >= 2
    stats = next(iter(with_params[0]["params"].values()))
    assert {"mean", "std", "absmax"} <= set(stats)
    # file really is line-delimited json
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_report_renders_html(tmp_path):
    storage = InMemoryStatsStorage()
    m = _model()
    m.set_listeners(StatsListener(storage))
    m.fit(_data(), n_epochs=4)
    out = render_report(storage, str(tmp_path / "report.html"))
    html = open(out).read()
    assert "Loss" in html and "svg" in html and "Data table" in html
    assert "data-pts" in html  # hover layer attached
    assert render_report(InMemoryStatsStorage(),
                         str(tmp_path / "empty.html")) is None


def _poison(m):
    import jax.numpy as jnp
    w = np.asarray(m.params_tree["layer_0"]["W"]).copy()
    w[0, 0] = np.nan
    m.params_tree["layer_0"]["W"] = jnp.asarray(w)


def test_nan_check_mode_names_offender(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_CHECK_NUMERICS", "1")
    m = _model(seed=3)
    _poison(m)
    it = _data()
    with pytest.raises(FloatingPointError,
                       match=r"Non-finite.*layer_0"):
        m.fit(it)


def test_nan_check_off_by_default():
    assert os.environ.get("DL4J_TPU_CHECK_NUMERICS", "") == ""
    m = _model(seed=3)
    _poison(m)
    m.fit(_data())  # silently NaNs, as DL4J does without the profiler flag


def test_profiler_listener_writes_trace(tmp_path):
    d = str(tmp_path / "trace")
    m = _model()
    m.set_listeners(ProfilerListener(d, start_iteration=2, n_iterations=2))
    m.fit(_data(), n_epochs=3)
    # a jax.profiler trace directory with at least one .xplane.pb inside
    found = []
    for root, _, files in os.walk(d):
        found += [f for f in files if f.endswith(".xplane.pb")]
    assert found, f"no trace written under {d}"
