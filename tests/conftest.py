import os
import sys

# tests run against the source tree; smoke tests must see the REAL device
# count (1 CPU device) — the 512-device XLA flag is set ONLY inside
# repro.launch.dryrun / subprocess-based sharding tests.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the per-client loop the batched engine is tested against: not a built-in
# engine, so the tests register it under a name of their own
from repro.fl.client import reference_engine  # noqa: E402
from repro.fl.engine import register_engine  # noqa: E402

register_engine("reference", reference_engine)
