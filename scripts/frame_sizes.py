#!/usr/bin/env python3
"""Which functions' frames differ in size between two trees.

    python3 scripts/frame_sizes.py <tree a> <tree b> [file ...]

For every code object of the named source files (left out: the two files
whose functions are on the Python stack while the certified program is
traced) prints those whose frame changed: locals + cells + free
variables, the operand stack, and their sum, in pointers.

Why it matters (root PERF.md section 6, PRs 29 and 46): CPython keeps
its frames in 16 KiB chunks, and where a chunk ends between the
bin-select's unrolled loop and the ``lax`` binds under it, every bind
maps and unmaps one: 0.8 to 1.5 s of a cell's ``first batch``.  Which
cell draws it follows the summed frame sizes of the stack above the
loop, so an edit that leaves every frame on that stack at its size
leaves every cell its draw, and this says so before the chip does.
Nothing is imported or run: the files are compiled, not executed.
"""

import sys
import types

DEFAULT_FILES = ("knn_tpu/ops/pallas_knn.py", "knn_tpu/parallel/sharded.py")


def slots(code) -> tuple:
    """(locals + cells + free variables, operand stack) of a code
    object, in pointers: its frame but for the fixed header."""
    return (len(set(code.co_varnames) | set(code.co_cellvars))
            + len(code.co_freevars), code.co_stacksize)


def frame_sizes(path: str) -> dict:
    """qualified name -> :func:`slots` of every code object of a file."""
    out = {}

    def walk(code, prefix):
        name = f"{prefix}.{code.co_name}" if prefix else code.co_name
        key, n = name, 1
        while key in out:  # lambdas and comprehensions share a name
            n += 1
            key = f"{name}#{n}"
        out[key] = slots(code)
        for const in code.co_consts:
            if isinstance(const, types.CodeType):
                walk(const, name)

    with open(path) as f:
        walk(compile(f.read(), path, "exec"), "")
    return out


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__)
        return 2
    a_root, b_root, *files = argv
    moved = 0
    for rel in files or DEFAULT_FILES:
        a, b = frame_sizes(f"{a_root}/{rel}"), frame_sizes(f"{b_root}/{rel}")
        for name in sorted(set(a) | set(b)):
            if a.get(name) != b.get(name):
                moved += 1
                print(f"{rel}: {name}: {a.get(name)} -> {b.get(name)}  "
                      f"sum {sum(a[name]) if name in a else None} -> "
                      f"{sum(b[name]) if name in b else None}")
    print(f"{moved} frames differ")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
