"""Are two runs' optimized HLO modules the same program? Compares, for every
module in the second ``--xla_dump_to`` directory, its instruction list with
the module of the same name in the first: metadata, the custom calls' opaque
payloads (a Mosaic kernel's holds its source lines) and the tables of source
files and locations (at the head of a TPU dump, at the end of others) left
out.

    python3 tools/hlo_same.py <dump of the first run> <dump of the second>

Exit code 1 where a module differs, has no partner, or holds no instruction
at all: a comparison of nothing is not a result.
"""
from __future__ import annotations

import glob
import os
import re
import sys

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def instructions(lines):
    """The instruction lines of one module's text, normalised."""
    out, in_table = [], False
    for line in lines:
        line = line.strip()
        if line in _TABLES:
            in_table = True
            continue
        if in_table:   # a table's rows are `<id> ...`; a blank line ends it
            if not line or re.match(r"^\d+ ", line):
                continue
            in_table = False
        if not line or line.startswith(("HloModule", "//", "#")):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        line = re.sub(r'backend_config="[^"]*"', "backend_config=<payload>",
                      line)
        line = re.sub(r"backend_config=\{.*$", "backend_config=<json>", line)
        out.append(line)
    return out


def compare(a, b):
    """Lines of ``a`` that differ from ``b``'s, or None where the lists are
    equal. Raises ValueError on an empty list."""
    if not a or not b:
        raise ValueError("no instructions to compare (%d against %d)"
                         % (len(a), len(b)))
    if len(a) != len(b):
        return [("%d instructions" % len(a), "%d instructions" % len(b))]
    return [(x, y) for x, y in zip(a, b) if x != y] or None


def _name(path):
    # module_0042.jit_step_s1.tpu_after_optimizations.txt -> jit_step_s1
    return re.sub(r"^module_\d+\.", "", os.path.basename(path)).split(".")[0]


def main(first, second):
    mine = {}
    for p in sorted(glob.glob(os.path.join(first,
                                           "*after_optimizations.txt"))):
        mine.setdefault(_name(p), []).append(p)
    built = sorted(glob.glob(os.path.join(second,
                                          "*after_optimizations.txt")))
    print("# modules: first run %d, second run %d"
          % (sum(map(len, mine.values())), len(built)))
    bad = not built
    for p in built:
        a = instructions(open(p))
        calls = sum('custom_call_target="tpu_custom_call"' in x for x in a)
        head = "%s: %d instructions, %d Mosaic calls: " % (
            _name(p), len(a), calls)
        best = None
        for q in mine.get(_name(p), []):
            try:
                diff = compare(a, instructions(open(q)))
            except ValueError as e:
                diff = [(str(e), "")]
            if best is None or len(diff or ()) < len(best[0] or ()):
                best = (diff, q)
        if best is None:
            print(head + "NO module of that name in the first run")
            bad = True
        elif best[0] is None:
            print(head + "instruction lists EQUAL (payloads and metadata "
                  "apart)")
        else:
            bad = True
            print(head + "%d lines differ from %s"
                  % (len(best[0]), os.path.basename(best[1])))
            for x, y in best[0][:2]:
                print("   second: %s\n   first:  %s" % (x[:300], y[:300]))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
