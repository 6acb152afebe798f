"""Host oracle for the fused AXPYDOT pipeline (paper §4.1): z in float32
as the kernel forms it, the dot in float64, so that at millions of
elements the oracle's own rounding stays far below a float32 tolerance."""
import numpy as np


def axpydot(a, x, y, w):
    z = np.float32(a) * np.asarray(x, np.float32) + np.asarray(y, np.float32)
    return np.dot(z.astype(np.float64), np.asarray(w, np.float64))[None]
