"""The comparison that decides ``correct``.

Every answer the window served is compared with the plain reference, as
of the rows ingested before the pump that served it.  Two numbers come
out, each held to the limit its configuration file states:

* ``exact_mismatch``: answers of the features the configuration states as
  exact (counts, MAX, LAST JOIN values) that differ from the reference in
  float32.  Limit 0.
* ``value_err``: the widest gap of the float32 folds (SUM, MEAN, STD and
  what is computed from them), each as a share of the larger of the
  reference's own magnitude and the feature's mean magnitude over the
  compared answers, so a value near 0 is not divided by 0.  A STD is
  compared as its square, the variance, at the scale of the window's mean
  square (``<feature>.meansq`` from the reference): a float32 variance
  E[x^2] - E[x]^2 keeps rounding of about 1e-7 of the mean square, which
  the square root would turn into a standard deviation far above it.

The control (``as_bf16``) is the reference with every stored and request
value held as bfloat16, the precision below the configuration's float32.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import reference as R


def tables(cfg: dict, log: Dict[str, list]) -> Dict[str, R.Table]:
    """The reference's tables from the ingest log (batches in ingest order)."""
    out = {}
    for name, batches in log.items():
        if not batches:
            continue
        tab = cfg["tables"][name]
        cols = {c: np.concatenate([b[c] for b in batches]) for c in batches[0]}
        out[name] = R.Table(cols[tab["key"]], cols["ts"], cols,
                            int(cfg["store"]["capacity"]))
    return out


def reference(ref_mod, cfg: dict, log, req: dict, cutoff: dict) -> dict:
    want = ref_mod.features(tables(cfg, log), req, cutoff, cfg)
    return {f: np.asarray(v, np.float64) for f, v in want.items()}


def compare(got: dict, want: dict, exact, squared, limits: dict) -> dict:
    mismatch = 0
    worst = 0.0
    for f, w in want.items():
        if f.endswith(".meansq"):
            continue
        g = np.asarray(got[f], np.float32)
        if f in exact:
            mismatch += int(np.sum(g != w.astype(np.float32)))
            continue
        if not len(w):
            continue
        g = g.astype(np.float64)
        mag = w
        if f in squared:
            g, w, mag = g * g, w * w, want[f + ".meansq"]
        scale = float(np.mean(np.abs(mag))) or 1.0
        err = np.abs(g - w) / np.maximum(np.abs(w), scale)
        # a NaN or inf answer is as far off as an answer can be
        err = np.where(np.isfinite(err), err, np.inf)
        worst = max(worst, float(np.max(err)))
    return {
        "exact_mismatch": {"value": mismatch,
                           "limit": limits["exact_mismatch"]},
        "value_err": {"value": worst, "limit": limits["value_err"]},
    }


def as_bf16(data):
    """Every float32 column rounded to bfloat16 (ingest log or requests)."""
    if isinstance(data, dict) and all(isinstance(v, list)
                                      for v in data.values()):
        return {t: [as_bf16(b) for b in bs] for t, bs in data.items()}
    return {c: (R.to_bf16(v) if np.asarray(v).dtype == np.float32 else v)
            for c, v in data.items()}
