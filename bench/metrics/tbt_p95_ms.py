"""Gap between consecutive output tokens of one request, 95th percentile,
ms: every gap whose later token reached the host inside the window, as the
host received it (tokens of one fused horizon arrive together: one gap, then
gaps of 0)."""
from harness import stats


def read(run):
    gaps = stats.token_gaps(run)
    return None if not gaps else stats.percentile(gaps, 95) * 1e3
