"""A check run by hand on the chip: does the program's own trip
comparison (models/degrade.py ``ratio_trips``, with each threshold
compiled by ``ratio_trip_fraction``) give upstream's verdict,
``k * 1.0d / n > threshold`` in doubles, at every count pair near each
threshold? It jits the program's function over every pair with k within
two of ``n * threshold`` for totals up to 200,000 and counts the verdicts
that differ from numpy's float64, at ties and elsewhere.

    python3 benchmark/tests/tpu_breaker_ties.py
"""

import json
import sys
from fractions import Fraction
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from sentinel_tpu.models import degrade as D  # noqa: E402


def main():
    out = {"device": jax.devices()[0].device_kind}
    trips = jax.jit(D.ratio_trips)
    n = np.arange(1, 200_001, dtype=np.int64)
    for thr, at_one in ((0.6, True), (0.2, False), (0.5, False),
                        (1.0, True), (1.0, False)):
        num, den = D.ratio_trip_fraction(thr, trips_at_one=at_one)
        base = np.floor(n * thr).astype(np.int64)
        ks = np.concatenate([base + d for d in (-2, -1, 0, 1, 2)])
        ns = np.tile(n, 5)
        keep = (ks >= 0) & (ks <= ns)
        ks, ns = ks[keep].astype(np.int32), ns[keep].astype(np.int32)
        got = np.asarray(jax.device_get(trips(ks, ns, np.int64(num),
                                              np.int64(den))))
        ratio = ks.astype(np.float64) / ns
        want = ratio > thr
        if at_one:
            want |= (ratio == 1.0) & (thr == 1.0)
        q = Fraction(str(thr))
        ties = (ks.astype(np.int64) * q.denominator
                == ns.astype(np.int64) * q.numerator)
        key = f"{thr}{'/slow' if at_one else ''}"
        out[key] = {"pairs": int(len(ks)), "ties": int(ties.sum()),
                    "verdicts_differ": int((got != want).sum()),
                    "verdicts_differ_at_ties": int(((got != want) & ties).sum())}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
