"""Fixture-gated parity tests — activate when driver-provisioned files appear.

The container has no egress (see docs/BENCHMARKS.md "Accuracy gates"), so
two reference-strength checks can't run on
materials we can produce ourselves:

1. stock-LightGBM interop (reference ``booster/LightGBMBooster.scala:458``
   round-trips through the real engine),
2. real-pretrained-weights fine-tune (reference DL gate
   ``test_deep_text_classifier.py:48-52``: real bert-base, accuracy > 0.5).

These tests are pre-wired to the requested fixture paths and SKIP with an
explicit message until the driver provisions them. Requested layout:

    tests/resources/fixtures/stock_lightgbm/model.txt
        — a model.txt written by stock `lightgbm` (any small binary model)
    tests/resources/fixtures/stock_lightgbm/data.csv
        — the feature matrix it was trained on (no header, floats)
    tests/resources/fixtures/stock_lightgbm/pred.csv
        — stock LightGBM's predict() probabilities on data.csv, one per line
    tests/resources/fixtures/bert-base-uncased/
        — HF checkpoint dir (config.json + model.safetensors + vocab.txt)
"""

import pathlib

import numpy as np
import pytest

FIXTURES = pathlib.Path(__file__).parent / "resources" / "fixtures"
STOCK_LGBM = FIXTURES / "stock_lightgbm"
BERT_DIR = FIXTURES / "bert-base-uncased"


@pytest.mark.skipif(not (STOCK_LGBM / "model.txt").exists(),
                    reason="no driver-provisioned stock-LightGBM fixture "
                           f"({STOCK_LGBM}/model.txt); egress is blocked and "
                           "the lightgbm wheel is not in-container — see "
                           "docs/BENCHMARKS.md")
def test_stock_lightgbm_model_import_parity():
    """A model.txt written by STOCK LightGBM must load through
    parse_lightgbm_string and reproduce stock predictions exactly."""
    from synapseml_tpu.gbdt import parse_lightgbm_string

    booster = parse_lightgbm_string((STOCK_LGBM / "model.txt").read_text())
    X = np.loadtxt(STOCK_LGBM / "data.csv", delimiter=",", dtype=np.float32)
    expected = np.loadtxt(STOCK_LGBM / "pred.csv", dtype=np.float64)
    got = np.asarray(booster.predict(X)).reshape(expected.shape)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


@pytest.mark.skipif(not (BERT_DIR / "config.json").exists(),
                    reason="no driver-provisioned bert-base-uncased checkpoint "
                           f"({BERT_DIR}); egress is blocked — see "
                           "docs/BENCHMARKS.md")
@pytest.mark.slow
def test_real_bert_weights_finetune_gate():
    """The reference's real-weights DL gate: fine-tune real bert-base on a
    small real text task and require accuracy > 0.5 (ref
    test_deep_text_classifier.py:48-52). Uses a locally-constructed real
    sentiment subset if no dataset fixture is present."""
    import synapseml_tpu as st
    from synapseml_tpu.models import DeepTextClassifier

    rows = []
    data_file = FIXTURES / "text_classification.csv"
    if data_file.exists():  # optional: driver-provisioned real dataset
        import csv

        with open(data_file) as f:
            for r in csv.DictReader(f):
                rows.append({"text": r["text"], "label": int(r["label"])})
    else:  # tiny real-English sentiment set (hand-written, still real text)
        pos = ["a wonderful film with a great cast", "truly excellent and moving",
               "I loved every minute of it", "brilliant, funny, and heartfelt",
               "one of the best this year", "a joy from start to finish"]
        neg = ["a dull and lifeless mess", "I hated the wooden acting",
               "boring from start to finish", "a complete waste of time",
               "the worst film of the year", "clumsy, tedious, and flat"]
        rows = ([{"text": t, "label": 1} for t in pos]
                + [{"text": t, "label": 0} for t in neg]) * 4
    df = st.DataFrame.from_rows(rows)
    model = DeepTextClassifier(checkpoint=str(BERT_DIR), num_classes=2,
                               batch_size=8, max_token_len=32,
                               learning_rate=3e-5, num_train_epochs=2).fit(df)
    out = model.transform(df)
    acc = float(np.mean(out.collect_column("prediction")
                        == out.collect_column("label")))
    assert acc > 0.5, f"real-weights fine-tune accuracy {acc} below gate 0.5"
