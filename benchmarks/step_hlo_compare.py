"""Is the compiled train step of two trees the same program?

    XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text \\
               --xla_dump_hlo_module_re=jit_step" <a run that compiles>
    JAX_PLATFORMS=cpu python benchmarks/step_hlo_compare.py <dir_a> <dir_b>

compares every `*jit_step*after_optimizations.txt` of two XLA dump
directories once what only says where the source was is taken out: every
`metadata={...}`, the FileNames / FunctionNames / FileLocations /
StackFrames tables that metadata's ids point into, and, in each Mosaic
kernel's serialized MLIR (`"body":"<base64>"`, which carries call-stack
locations), everything but its text printed without locations. Prints one
sha256 a side and exits 1 unless both sides hold the same set of programs.
A refactor that only moves source lines passes; any other change to the
program does not."""
import base64
import glob
import hashlib
import os
import re
import sys

TABLES = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def strip(text: str) -> str:
    out, i = [], 0
    key = ", metadata={"
    while True:
        j = text.find(key, i)
        if j < 0:
            out.append(text[i:])
            break
        out.append(text[i:j])
        k, depth, quoted = j + len(key), 1, False
        while depth:
            c = text[k]
            if c == '"' and text[k - 1] != "\\":
                quoted = not quoted
            elif not quoted and c == "{":
                depth += 1
            elif not quoted and c == "}":
                depth -= 1
            k += 1
        i = k
    lines, skipping = [], False
    for line in "".join(out).split("\n"):
        if line.strip() in TABLES:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            lines.append(line)
    return _BODY.sub(_kernel_digest, "\n".join(lines))


_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_ctx = None


def _kernel_digest(match) -> str:
    global _ctx
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    if _ctx is None:
        _ctx = jmlir.make_ir_context()
        _ctx.allow_unregistered_dialects = True
    raw = base64.b64decode(match.group(1))
    try:
        with _ctx:
            module = ir.Module.parse(raw)
            asm = module.operation.get_asm(enable_debug_info=False)
    except Exception:
        # a kernel the compiler wrote itself (ragged-dot): no Python call
        # stack in it, and dialects this context cannot parse; held to its
        # bytes
        return '"body":"raw-sha256:%s"' % hashlib.sha256(raw).hexdigest()
    return '"body":"mlir-sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def programs(dump_dir: str) -> dict:
    """{sha256 of the stripped text: a file that holds it}: a dump holds
    one file for each compile, and a step compiled twice is one program."""
    found = {}
    for path in sorted(glob.glob(os.path.join(
            dump_dir, "*jit_step*after_optimizations.txt"))):
        with open(path) as f:
            digest = hashlib.sha256(strip(f.read()).encode()).hexdigest()
        found.setdefault(digest, os.path.basename(path))
    return found


def main(argv) -> int:
    a, b = (programs(d) for d in argv)
    for side, found in zip(argv, (a, b)):
        for digest, name in found.items():
            print(f"{digest}  {side}/{name}")
    same = bool(a) and set(a) == set(b)
    print("SAME PROGRAM" if same else "DIFFERENT (or nothing dumped)")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
