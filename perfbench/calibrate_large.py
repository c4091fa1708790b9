#!/usr/bin/env python3
"""``calibrate.py`` for the cells of the ``train_large`` driver.

    python3 perfbench/calibrate_large.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1] [--out calib.jsonl]

The same readings, arguments and output lines as ``calibrate.py``, taken
with that driver's program rounds and sequential reference
(``drivers/train_large.py``, ``readings``).
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import calibrate  # noqa: E402
from pbench import harness  # noqa: E402


def readings(run, seed, controls):
    drv = harness.load_module(harness.BENCH / "drivers" / "train_large.py",
                              "drv_large")
    return drv.readings(run, seed, controls)


if __name__ == "__main__":
    calibrate.readings = readings
    sys.exit(calibrate.main())
