"""End-to-end walkthrough: train -> export -> independent verify -> serve.

The reference's quickstart story (train a LightGBMClassifier, save the
native model, score it elsewhere, stand it up behind Spark Serving) on the
TPU-native stack.  Runs on any jax backend; pass ``--cpu`` to force the
CPU backend.

    python samples/train_export_serve.py [--cpu]
"""

import json
import os
import sys
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    if "--cpu" in sys.argv[1:]:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from mmlspark_tpu.gbdt import (LightGBMClassificationModel,
                                   LightGBMClassifier)

    # ------------------------------------------------------------------ 1
    # Train on a synthetic adult-income-shaped table
    rng = np.random.default_rng(7)
    n = 20_000
    X = rng.normal(size=(n, 16)).astype(np.float32)
    y = ((X[:, 0] + 0.6 * X[:, 1] * X[:, 2] + np.sin(X[:, 3])
          + rng.normal(size=n) * 0.5) > 0).astype(np.float64)
    table = {"features": X, "label": y}

    model = LightGBMClassifier(
        numIterations=50, numLeaves=31, learningRate=0.1,
        verbosity=0).fit(table)
    from sklearn.metrics import roc_auc_score
    proba = np.asarray(model.transform(table)["probability"])[:, 1]
    print(f"[1] trained: train AUC = {roc_auc_score(y, proba):.4f}")

    # ------------------------------------------------------------------ 2
    # Export to the stock-LightGBM text format and reload
    path = "/tmp/mmlspark_tpu_sample_model.txt"
    model.saveNativeModel(path)
    print(f"[2] exported LightGBM v3 text model -> {path} "
          f"({os.path.getsize(path)} bytes)")

    # ------------------------------------------------------------------ 3
    # Independent verification: score a few rows with the spec-following
    # reference walker from the golden-interop test suite (no framework
    # code on that path) and compare to the framework's predictions.
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tests"))
    from test_golden_interop import _reference_predict
    reloaded = LightGBMClassificationModel.loadNativeModelFromFile(path)
    sample = X[:64]
    margins = np.asarray(reloaded.getModel().predict_margin(sample)).ravel()
    ours = 1.0 / (1.0 + np.exp(-margins))      # walker emits probabilities
    independent = _reference_predict(open(path).read(), sample)
    np.testing.assert_allclose(ours, independent, rtol=1e-5, atol=1e-6)
    print(f"[3] independent walker agrees on {len(sample)} rows "
          f"(max |diff| = {np.max(np.abs(ours - independent)):.2e})")

    # ------------------------------------------------------------------ 4
    # Serve it: HTTP in, batched model transform, HTTP out
    import threading

    from mmlspark_tpu.io.serving import HTTPServer, serve_forever

    server = HTTPServer(port=0).start()
    stop = threading.Event()

    def transform(t):
        feats = np.asarray(t["features"], np.float32)   # (rows, 16)
        out = reloaded.transform({"features": feats})
        return t.withColumn("reply", np.asarray([
            {"probability": float(p[1])}
            for p in np.asarray(out["probability"])], dtype=object))

    worker = threading.Thread(
        target=serve_forever,
        args=(server, transform, "reply"),
        kwargs={"max_rows": 32, "stop_event": stop}, daemon=True)
    worker.start()

    req = json.dumps({"features": X[0].tolist()}).encode()
    resp = urllib.request.urlopen(urllib.request.Request(
        f"http://{server.host}:{server.port}/", data=req,
        headers={"Content-Type": "application/json"}), timeout=10)
    answer = json.loads(resp.read())
    stop.set()
    server.stop()
    expect = float(proba[0])
    assert abs(answer["probability"] - expect) < 1e-5
    print(f"[4] served: POST -> probability {answer['probability']:.4f} "
          f"(matches batch transform {expect:.4f})")
    print("sample complete.")


if __name__ == "__main__":
    main()
