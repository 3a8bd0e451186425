"""``sweep_rates.py`` for a cell whose mix names its own generator
(driver ``serve_open_loop_lm``): the same sweep, the same lines, with
``traffic_gen.open_loop_blocks`` (which ``sweep_rates`` calls by name)
standing for the mix's generator while it runs.

    python3 chipbench/tests/sweep_rates_lm.py --workload <cell> --seed <n> \
        --seconds <window> --rates 2,3,4,5
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "chipbench"))


def main():
    import run
    import sweep_rates
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args, _ = ap.parse_known_args()
    _bench, _cell, _config, mix, _extra = run.load_cell(args.workload,
                                                        args.rehearse)
    drv = run.load_module("drivers", mix["driver"])
    sys.path.insert(0, ROOT)
    from chipbench import traffic_gen
    traffic_gen.open_loop_blocks = drv.generator(mix)
    sweep_rates.main()


if __name__ == "__main__":
    main()
