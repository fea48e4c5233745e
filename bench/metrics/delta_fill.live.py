"""Mean over the window's waves of the delta buffer's occupancy (slots
used over slots) in the version each wave ran, as the live index
counted it when that version was published.  None without a live
index."""
import numpy as np


def read(w):
    fill = (w.live or {}).get("delta_fill")
    return float(np.mean(fill)) if fill is not None and fill.size else None
