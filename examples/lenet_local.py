"""LeNet-5 on MNIST, single chip.

Reference: example/lenetLocal + models/lenet/Train.scala:35 — the minimum
end-to-end slice (SURVEY.md section 7 step 3).  Runs on synthetic MNIST when
no --folder is given:

    python examples/lenet_local.py --maxIteration 20
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bigdl_tpu.models import run

if __name__ == "__main__":
    import sys
    run.main(["lenet-train"] + sys.argv[1:])
