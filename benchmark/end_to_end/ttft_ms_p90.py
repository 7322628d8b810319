"""90th percentile of the time to the first token, by NEAREST RANK over the
window's timed requests; a failed or refused request counts as slower than
any.

Judged only where at least ten timed requests lie beyond its rank
(``stats.MIN_BEYOND``; ``tests/bench/test_benchmark_gate.py`` counts them
from the traffic file's plan for every cell that lists this metric). In
``mistral-7b.chat`` that is the 93rd of 103 at 2.3 req/s. Over the 45
requests of 1.0 req/s it was the 41st with four beyond it, and ONE request
that caught the tick before the one it usually waits for moved the reading
from 692 to 637 ms (PERF.md section 6, PR 37).

Nearest rank and not interpolated: over four sets of six runs (my chip
runs, PR 37) the two spread alike (quartiles over median: 0.0028 | 0.0029,
0.0414 | 0.0487, 0.0055 | 0.0050, 0.0021 | 0.0020), so nothing is bought by
interpolating; the nearest rank is one request's own reading, which the
per-request table (``requests.json``) shows by its index, and a ``MISSED``
reading at or beside the rank needs no arithmetic on infinity."""

from benchmark import samples

DEVICE_METRIC = True
#: which percentile of its sample this is: a tier-1 test holds every cell
#: that lists the metric to ten timed requests beyond its rank
PERCENTILE = 90.0


def read(run):
    return samples.ttft_percentile_ms(run, PERCENTILE)
