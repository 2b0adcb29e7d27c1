"""``limits.py`` with another control of the configuration's reference put
in the int8 control's place. By hand, on the chip:

    python3 benchmark/limits_control.py --control state_bf16 \\
        --workload granite4h-chat-closed-64 --seeds 1,2,3 --seconds 30

Everything else is ``limits.py``'s: one deployment, a window a seed, the
program's reading and the control's, both judged with the cell's limits,
exit 1 unless the program is correct on every seed and the control on none.
The control's name travels to the rank as the ``control`` argument of the
service's ``finish``; a reference that names its controls
(``bench_reference_hybrid.CONTROLS``) refuses one it does not have.
"""

from __future__ import annotations

import argparse
import sys

import limits
import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True)
    args, rest = ap.parse_known_args(argv)
    load = R.load_file

    def load_file(path, prefix="bench_file_"):
        mod = load(path, prefix)
        measure = getattr(mod, "measure", None)
        if measure is not None:
            def named(*a, control=False, **kw):
                return measure(*a, control=control and args.control, **kw)
            mod.measure = named
        return mod

    R.load_file = load_file
    return limits.main(rest)


if __name__ == "__main__":
    sys.exit(main())
