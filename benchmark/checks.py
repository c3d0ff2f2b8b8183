"""Checks both drivers share; each returns (ok, numbers) and the numbers
are printed on an earlier line of the run."""

from __future__ import annotations

import math

from benchmark import reference


def evaluation(config: dict, w, test, reported_loss: float, reported_acc: float):
    """The objective and accuracy the fit reported for `w` on the test
    split, against the reference's over the whole split."""
    tol = config["tolerance"]
    ref_loss, ref_acc = reference.evaluate(
        config["model"], w, test.indices, test.values, test.labels,
        float(config["lam"]))
    d_loss, d_acc = abs(reported_loss - ref_loss), abs(reported_acc - ref_acc)
    ok = d_loss <= float(tol["eval_loss_abs"]) and d_acc <= float(tol["eval_acc_abs"])
    return ok, {"reported_loss": reported_loss, "reference_loss": ref_loss,
                "reported_acc": reported_acc, "reference_acc": ref_acc,
                "loss_abs_err": d_loss, "acc_abs_err": d_acc,
                "loss_tol": tol["eval_loss_abs"], "acc_tol": tol["eval_acc_abs"]}


def quality(quality_file: dict, value) -> tuple:
    """The loss at the fixed budget, inside the band taken from the spread
    across seeds on the chip.  A budget the run did not reach fails."""
    lo, hi = (float(b) for b in quality_file["loss_band"])
    ok = value is not None and math.isfinite(value) and lo <= value <= hi
    return ok, {"budget_loss": value, "loss_band": quality_file["loss_band"]}


def all_finite(values) -> bool:
    return all(v is not None and math.isfinite(float(v)) for v in values)
