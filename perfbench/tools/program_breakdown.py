"""Where a traced serving window's time went, by the program's own names:
device seconds by compiled program, the ``serve.*`` spans' counts and
seconds, and the idle gaps by the innermost ``bench.*`` or ``serve.*``
span, beside ``trace.reduce``'s busy and window seconds.

    python3 perfbench/tools/program_breakdown.py <trace.xplane.pb>
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import program_trace, trace  # noqa: E402


def main(path: str) -> int:
    rec = program_trace.load(path)
    old = trace.reduce(rec)
    new = program_trace.reduce(rec)
    print(json.dumps({"busy_s": old["busy_s"], "window_s": old["window_s"],
                      "module_s_total": sum(new["module_s"].values()),
                      **new}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
