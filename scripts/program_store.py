#!/usr/bin/env python
"""The program store's directory, from outside a server (no chip needed).

    python scripts/program_store.py list   [DIR]
    python scripts/program_store.py verify [DIR]
    python scripts/program_store.py clear  [DIR]

DIR defaults to `<compile_cache_dir()>/programs`, where a server, bench.py
and chip_smoke.py keep it (kafka_tpu/runtime/program_store.py).

`list`: key, label, bytes, age of every entry, least recently used first.
`verify`: every entry THIS tree would itself look up (same sources,
environment, jax configuration and platform) is traced and lowered again
from the recipe kept beside its blob, and the text's digest compared with the
stored one: `equal` is the offline proof that the key held everything the
trace read.  Entries of other trees are `stale`, entries made on another
platform `skipped`.  Exit code 1 if any entry `differs`.  The entries one
server process wrote are lowered together in a process of their own
(`verify-group PATH ...`), in the order they were made, so that each program
finds the traces it found there and no other model's; this process never
touches jax, so on a chip the children have it to themselves.
`clear`: unlink every entry.
"""

import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kafka_tpu.runtime import compile_log, program_store  # noqa: E402


def verify_group(paths) -> int:
    compile_log.flat_locations()  # lower as a process with the cache on
    for path in paths:
        print("%s\t%s: %s" % (path, *program_store.verify_entry(path)),
              flush=True)
    return 0


def verify(found) -> dict:
    """{path: verdict} of every entry, a child process a writing process."""
    groups = {}
    for path, _, _ in found:
        meta = program_store.read_meta(path) or {}
        groups.setdefault(meta.get("process", path), []).append(
            (meta.get("created", 0.0), path))
    verdicts = {}
    for group in groups.values():
        paths = [path for _, path in sorted(group)]
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "verify-group",
             *paths], capture_output=True, text=True)
        for line in child.stdout.splitlines():
            path, _, verdict = line.partition("\t")
            if path in paths:
                verdicts[path] = verdict
        for path in paths:
            verdicts.setdefault(path, f"failed: exit code {child.returncode} "
                                + (child.stderr.strip().splitlines()
                                   or ["no output"])[-1][:200])
    return verdicts


def main(argv) -> int:
    if argv[:1] == ["verify-group"]:
        return verify_group(argv[1:])
    if not argv or argv[0] not in ("list", "verify", "clear"):
        print(__doc__)
        return 2
    root = argv[1] if len(argv) > 1 else os.path.join(
        compile_log.compile_cache_dir(), program_store.DIR_NAME)
    found = program_store.entries(root)
    if argv[0] == "clear":
        for path, _, _ in found:
            os.unlink(path)
        print(f"{len(found)} entries removed from {root}")
        return 0
    now, bad = time.time(), 0
    verdicts = verify(found) if argv[0] == "verify" else {}
    for path, size, mtime in found:
        name = os.path.basename(path)[:12]
        meta = program_store.read_meta(path) or {}
        row = f"{name}  {meta.get('label', '?'):28s} {size:>11d} B"
        if argv[0] == "list":
            print(f"{row}  {now - mtime:9.0f} s")
            continue
        verdict = verdicts[path]
        bad += not verdict.startswith(("equal", "stale", "skipped"))
        print(f"{row}  {verdict}")
    print(f"{len(found)} entries, {sum(e[1] for e in found)} bytes in {root}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
