"""95th percentile of every gap between consecutive output tokens whose
later token came in the window."""
import numpy as np


def read(rec):
    s = rec.get("serve")
    return None if s is None or not s["itl_ms"] else float(np.percentile(s["itl_ms"], 95))
