"""Place batched inference requests on a fleet's decode slots with Megha.

Demonstrates the paper's architecture as the control plane of an inference
fleet: 2 pods x 16 decode slots, 2 GM frontends with eventually-consistent
views of slot occupancy.  Each request holds its slot for its generation
length in engine ticks (no model runs).

    PYTHONPATH=src python examples/serve_cluster.py
"""

import sys

from repro.launch.serve import main

if __name__ == "__main__":
    if len(sys.argv) == 1:
        sys.argv += [
            "--requests", "120", "--pods", "2", "--slots", "16",
            "--frontends", "2",
        ]
    main()
