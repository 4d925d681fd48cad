"""``itl_p90_ms`` in rag-closed, where it is a per-layer reading that
moves ``output_tok_per_s``: there the 90th percentile of the gaps falls
on the edge between two clusters of prefill steps, so it jumps by a
fifth with the order in which requests come, and cannot hold a bound
end to end."""
from pathlib import Path

import harness

read = harness.reader(Path(__file__).resolve().parents[2], "itl_p90_ms")
