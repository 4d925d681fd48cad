"""``prefill_step_share``, read in the cells that report no ``itl_p90_ms`` end
to end (rag-closed), where it moves ``output_tok_per_s``."""
from pathlib import Path

import harness

read = harness.reader(Path(__file__).resolve().parents[2], "prefill_step_share")
