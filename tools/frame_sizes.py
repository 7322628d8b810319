"""Which functions' interpreter frames differ in size between two checkouts.

    python tools/frame_sizes.py <other checkout> [file under the package ...]

CPython 3.12 keeps a thread's frames in 16 KiB chunks and maps and unmaps a
chunk each time a call crosses a chunk's end, so where JAX's lowering has an
inner loop astride such a boundary it runs half as fast again; where the
boundary falls is the sum of the frame sizes of everything on the stack at
a program's first call. A frame more, or a local more in ``step()``, moves
it: PR 41 measured 0.33 s a prefill program, 6 s of chat's warm ``setup_s``,
for one wrapper's frame (PERF.md §6). This prints, for the engine's drive
path by default, every function whose frame (locals, cells and value stack,
in slots of 8 bytes) is not the other checkout's: an empty list says a
change left the stack under the step programs where it was.
"""
import os
import sys
import types

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
PACKAGE = "distributed_llm_inference_tpu"
DRIVE_PATH = ("engine/engine.py", "engine/plan.py", "serving/backends.py")


def frame_sizes(path):
    out = {}

    def walk(code):
        for const in code.co_consts:
            if isinstance(const, types.CodeType):
                names = (const.co_varnames, const.co_cellvars, const.co_freevars)
                out[const.co_qualname] = (
                    sum(map(len, names)) + const.co_stacksize
                )
                walk(const)

    with open(path) as f:
        walk(compile(f.read(), path, "exec"))
    return out


def main(argv):
    other, files = argv[0], argv[1:] or DRIVE_PATH
    for name in files:
        here = frame_sizes(os.path.join(HERE, PACKAGE, name))
        there = frame_sizes(os.path.join(other, PACKAGE, name))
        for fn in sorted(set(here) | set(there)):
            if here.get(fn) != there.get(fn):
                print(f"{name}  {fn}: {there.get(fn)} -> {here.get(fn)}")


if __name__ == "__main__":
    main(sys.argv[1:])
