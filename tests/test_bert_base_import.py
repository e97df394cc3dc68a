"""Import at REAL scale (round-2 review item 3): a BERT-base-SIZED
(12x768, 30522 vocab, ~110M params, 438 MB frozen pb) random-init
graph must import, match TF goldens elementwise, rewrite to fused
attention, and take a fine-tune step.

The fixture is generated on first run with the installed
tensorflow/transformers (~3 min) and cached under /tmp — it is far
too large to commit (the ``dl4j-test-resources`` external-artifact
pattern).  Generation lives in ``utils/bert_fixture.py``, shared with
``bench.py``'s imported-graph fine-tune benchmark.

t=512 (round-3 review item 1): >= kernels.flash_attention._FLASH_MIN_T,
so the imported fused path exercises the Pallas flash route — the
r2-era t=64 fixture only ever hit the XLA fallback."""
import numpy as np
import pytest

from deeplearning4j_tpu.utils.bert_fixture import (
    attach_classifier_head as _ensure_cls_head, ensure_bert_base_fixture)


@pytest.fixture(scope="module")
def bert_base():
    pb, gold = ensure_bert_base_fixture(t=512)
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    return import_frozen_pb(pb), np.load(gold)


def test_bert_base_import_scale_and_parity(bert_base):
    sd, g = bert_base
    n_var = sum(1 for v in sd.vars.values() if v.var_type == "VARIABLE")
    n_params = sum(
        int(np.prod(sd.values[v.name].shape))
        for v in sd.vars.values() if v.var_type == "VARIABLE")
    assert n_var >= 190, n_var             # 12 layers x 16 + emb + pooler
    assert n_params > 100e6, n_params      # genuinely BERT-base-sized
    out = sd.output({"i": g["ids"], "m": g["mask"], "t": g["tt"]},
                    ["Identity", "Identity_1"])
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=2e-5)
    np.testing.assert_allclose(np.asarray(out["Identity_1"]),
                               g["pooler"], atol=2e-5)


def test_bert_base_fused_attention_parity(bert_base):
    from deeplearning4j_tpu.autodiff.rewrites import fuse_attention
    from deeplearning4j_tpu import kernels as fa
    sd, g = bert_base
    assert fuse_attention(sd) == 12        # one site per encoder layer
    fa.reset_route_log()
    out = sd.output({"i": g["ids"], "m": g["mask"], "t": g["tt"]},
                    ["Identity"])
    # route-taken probe (round-3 review): at t=512 every one of the 12
    # imported sites must TRACE through the Pallas flash kernel, not
    # the XLA fallback — _flash_applicable's opinion is not trusted.
    routes = fa.route_log()
    assert len(routes) == 12, routes
    assert all(r[0] == "flash" for r in routes), routes
    np.testing.assert_allclose(np.asarray(out["Identity"]),
                               g["last_hidden"], atol=2e-5)




def test_bert_base_finetune_step(bert_base):
    """One full fine-tune step over all ~110M imported parameters:
    loss finite, parameters move."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.optimize.updaters import Sgd
    sd, g = bert_base
    _ensure_cls_head(sd)
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=1e-3),
        data_set_feature_mapping=["i", "m", "t"],
        data_set_label_mapping=["labels"]))
    probe = "tf_bert_model/bert/encoder/layer_._0/attention/self/" \
            "query/Tensordot/ReadVariableOp/resource"
    before = sd.values[probe].copy()
    ds = MultiDataSet([g["ids"], g["mask"], g["tt"]],
                      [np.asarray([0, 1], np.int32)])
    losses = sd.fit([ds], n_epochs=1)
    assert np.isfinite(losses).all(), losses
    assert not np.allclose(sd.values[probe], before)  # encoder trained


def test_bert_base_finetune_bf16_amp_flash_route(bert_base):
    """BASELINE config 4's training configuration: bf16 AMP
    (TrainingConfig.compute_dtype) with the flash kernel verifiably in
    the TRAIN trace.  Master weights stay f32."""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu import kernels as fa
    from deeplearning4j_tpu.autodiff.rewrites import fuse_attention
    from deeplearning4j_tpu.optimize.updaters import Sgd
    sd, g = bert_base
    if not any(n.op_name == "fused_attention" for n in sd.ops):
        assert fuse_attention(sd) == 12     # standalone-run safety
    _ensure_cls_head(sd)
    sd.set_training_config(TrainingConfig(
        updater=Sgd(learning_rate=1e-3),
        data_set_feature_mapping=["i", "m", "t"],
        data_set_label_mapping=["labels"],
        compute_dtype="bfloat16"))
    sd._fn_cache.clear()
    fa.reset_route_log()
    ds = MultiDataSet([g["ids"], g["mask"], g["tt"]],
                      [np.asarray([1, 0], np.int32)])
    losses = sd.fit([ds], n_epochs=1)
    assert np.isfinite(losses).all(), losses
    routes = fa.route_log()
    assert len(routes) == 12 and all(r[0] == "flash" for r in routes), \
        routes
    probe = "tf_bert_model/bert/encoder/layer_._0/attention/self/" \
            "query/Tensordot/ReadVariableOp/resource"
    assert sd.values[probe].dtype == np.float32  # master weights f32
