"""What the predict and the serve drivers share: the configuration's
serving model as a `Booster`, and the comparison of device scores with
the plain reference."""
import numpy as np

from benchmarks.lib import reference, synth

#: the f32 device predictor against a float64 reference
#: (chip_smoke.PREDICT_RTOL/ATOL, tests/test_device_predictor.py)
PREDICT_RTOL, PREDICT_ATOL = 1e-5, 1e-6


def build_model(run):
    """The configuration's serving model and its `Booster`."""
    from lightgbm_tpu.basic import Booster
    sm = run.config["serving_model"]
    with run.timed("model_s"):
        model = synth.serving_model(sm["trees"], sm["num_leaves"],
                                    run.config["features"], (run.seed, 3))
        text = model.save_model_to_string()
        bst = Booster(model_str=text)
    return model, text, bst


def check_against_reference(model, rows, got, sample, seed):
    """Largest error of `got` against the plain reference on a seeded
    sample of `rows`, and whether it is inside the tolerance."""
    pick = np.random.default_rng(seed).choice(
        len(rows), size=min(sample, len(rows)), replace=False)
    want = reference.sigmoid(reference.predict_raw(
        model.trees, rows[pick].astype(np.float64)))
    err = float(np.abs(got[pick] - want).max())
    ok = bool(np.allclose(got[pick], want, rtol=PREDICT_RTOL,
                          atol=PREDICT_ATOL))
    return {"sample": int(len(pick)), "max_abs_err": err, "ok": ok}
