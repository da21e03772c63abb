#!/usr/bin/env python3
"""Which functions' frames changed size between two trees: a frame is its
code's locals (cells and free variables among them) + its stack, and the
summed sizes of the frames under a call site decide whether that site falls
on the edge of one of CPython's 16 KiB frame chunks, where every call maps
and unmaps memory (``hack/stack_chunk_cliff.py``; PERF.md §6, PR 47). A
function that every program's ops have on their stack when they are traced
(``_forward_impl_grouped``, ``step_program``'s ``program``, ``MiniEngine.step``,
...) should keep its size where a PR does not mean to move set-up time.

    python3 hack/frame_sizes.py <parent tree> [<tree, default .>] [files ...]

Compiles the files (default: ``models/llama.py`` and ``models/engine.py``),
imports nothing, and prints ``qualified name: slots before -> after``.
"""

from __future__ import annotations

import sys
from pathlib import Path

FILES = ("llmd_kv_cache_tpu/models/llama.py",
         "llmd_kv_cache_tpu/models/engine.py")


def frames(path: Path) -> dict:
    """``{qualified name: locals + stack slots}`` of every code object."""
    sizes: dict = {}

    def walk(code, prefix):
        for const in code.co_consts:
            if hasattr(const, "co_code"):
                name = f"{prefix}{const.co_name}"
                sizes[name] = (const.co_nlocals + len(const.co_cellvars)
                               + len(const.co_freevars) + const.co_stacksize)
                walk(const, name + ".")

    walk(compile(path.read_text(), str(path), "exec"), "")
    return sizes


def main(argv) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    before_root = Path(argv[0])
    after_root = Path(argv[1]) if len(argv) > 1 else Path(".")
    moved = 0
    for rel in argv[2:] or FILES:
        before, after = frames(before_root / rel), frames(after_root / rel)
        for name in sorted(before.keys() | after.keys()):
            if before.get(name) != after.get(name):
                moved += 1
                print(f"{rel}: {name}: {before.get(name)} -> "
                      f"{after.get(name)}")
    print(f"{moved} frame(s) differ")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
