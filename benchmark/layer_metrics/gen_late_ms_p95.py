"""95th percentile of how late the load generator sent a request (sent -
due). Validity of an open-loop cell: above a tenth of the latency limit
the run is marked incorrect, so a starved generator is not read as a fast
server."""
from benchmark.harness import stats

LAYER = "load generator (benchmark/harness/loadgen.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "first_reply_ms_p95"


def read(record):
    late = record.get("late_ms")
    if late is None or len(late) == 0:
        return None
    return stats.percentile(late, 95)
