"""End-to-end example: serve a model through the full COACH system —
offline partition, real JAX end/cloud segments with the quantized wire,
semantic cache, early exits, adaptive precision, pipeline accounting.

  PYTHONPATH=src python examples/collaborative_serving.py \
      [--arch h2o-danube-3-4b] --smoke [--requests 200] [--correlation high]

Without ``--smoke`` the model runs at its published widths (a chip's
worth of weights).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.launch.serve import main  # the launcher IS the driver

if __name__ == "__main__":
    main()
