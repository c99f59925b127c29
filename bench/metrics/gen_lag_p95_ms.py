"""Load generator: 95th percentile of how late the generator sent the
window's suggests (actual send minus due time), in ms.  A starved
generator shows here, not as a slow server."""
import numpy as np


def read(run):
    lag = [r["sent"] - r["due"] for r in run.gen["suggests"]
           if r["phase"] == "window"]
    return 1e3 * float(np.quantile(lag, 0.95)) if lag else None
