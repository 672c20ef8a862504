"""The program store: a warm boot LOADS its step programs (ISSUE 67).

JAX's persistent compile cache is keyed by the LOWERED module, so a reboot
of the same code on the same chip traces every step program to a jaxpr and
lowers it to a module (each Pallas call traced and lowered anew) only to
look up an executable it already has.  This store sits beside that cache,
under ``<compile_cache_dir()>/programs/``, and is keyed WITHOUT tracing:

* **Cold** (no entry): the first call of a step program lowers and compiles
  as before (``jit.lower(*args).compile()``, through the persistent cache),
  serialises the executable with its in/out trees and writes the entry
  atomically.  **Warm** (entry found): ``deserialize_and_load``; no trace,
  no lowering, no cache lookup.  Either way the calls that follow go through
  the ``Compiled``'s own fast path.
* **The key** holds everything a trace could read (`program_key`): a digest
  of every ``.py`` of the package, the jax / jaxlib / PJRT versions, the
  device, the ``_PROGRAMS`` key and label, the arguments' tree, shapes,
  dtypes, weak types and shardings, the donated positions, the environment
  (`KEY_ENV`) and jax's trace context.  A key that cannot be computed is no
  key: the program is left to the jit.
* **No knob.**  The store is on exactly where
  ``compile_log.enable_compile_cache()`` was called.  Only programs whose
  arguments live on ONE device are stored, under a key that names it; a
  program over a mesh is left to the jit.
* An entry is unpickled: the store trusts its directory as the compile
  cache trusts its own.

``scripts/program_store.py`` lists, verifies and clears a directory.
"""

from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading
import time
import uuid
import zlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

logger = logging.getLogger("kafka_tpu.program_store")

DIR_NAME = "programs"
SUFFIX = ".prog"
# first line of an entry; bump when the layout below changes
MAGIC = b"kafka_tpu program store 1\n"
# the directory's size cap: least recently used entries go first (a load
# touches its file).  A cell's programs are tens of MB each on the chip.
MAX_BYTES = 8 << 30
DONATED = (1, 2)  # every step program donates its pools

PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Environment a trace or a compile can read: every KAFKA_TPU_* value but
# those below, and the two variables XLA and libtpu take their flags from.
KEY_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
# KAFKA_TPU_* names no traced code reads (tests/test_program_store.py scans
# models/, ops/, parallel/ and runtime/step_programs.py for them): the
# profiler switch wraps host dispatches in annotations, and a traced
# benchmark run sets it, which must not cost the run its warm boot.
HOST_ONLY_ENV = ("KAFKA_TPU_PROFILING",)

COUNTERS = ("store_hits", "store_misses", "store_fallbacks")
_lock = threading.Lock()
_counts: Dict[str, int] = {k: 0 for k in COUNTERS}
_load_s = 0.0
_dir: Optional[str] = None
_write_failed = False
# which process wrote an entry: `verify` lowers a process's entries together,
# in the order they were made, as that process did
_PROCESS = uuid.uuid4().hex


def enable(cache_dir: Optional[str]) -> None:
    """Turn the store on under `cache_dir` (compile_log.enable_compile_cache)
    or, with None, off: `wrap` then returns the jit it is given."""
    global _dir
    _dir = os.path.join(cache_dir, DIR_NAME) if cache_dir else None


def directory() -> Optional[str]:
    return _dir


def counters() -> Dict[str, int]:
    """/metrics `compiles.store_*`: programs loaded from the store, programs
    compiled and written, variants handed back to the jit."""
    with _lock:
        return dict(_counts)


def load_seconds() -> float:
    """/metrics `boot.store_load_s`: seconds spent reading and loading
    entries (a process's loads are its boot's and its rebuilds')."""
    with _lock:
        return _load_s


def reset_for_tests() -> None:
    global _write_failed, _load_s
    with _lock:
        for k in _counts:
            _counts[k] = 0
        _load_s = 0.0
    _write_failed = False
    source_digest.cache_clear()


def _count(name: str, load_s: float = 0.0) -> None:
    global _load_s
    with _lock:
        _counts[name] += 1
        _load_s += load_s


# ----------------------------------------------------------------------
# the key
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=4)
def source_digest(root: str = PACKAGE_ROOT) -> str:
    """sha256 over every .py under `root`, path and bytes, in path order."""
    h = hashlib.sha256()
    paths = []
    for base, dirs, files in os.walk(root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        paths.extend(os.path.join(base, f) for f in files if f.endswith(".py"))
    for path in sorted(paths):
        h.update(os.path.relpath(path, root).encode() + b"\0")
        with open(path, "rb") as f:
            data = f.read()
        h.update(str(len(data)).encode() + b"\0" + data)
    return h.hexdigest()


def key_env() -> List[Tuple[str, str]]:
    env = os.environ
    return sorted((k, v) for k, v in env.items()
                  if (k.startswith("KAFKA_TPU_") and k not in HOST_ONLY_ENV)
                  or k in KEY_ENV)


class Signature(NamedTuple):
    """What a call's arguments are, leaf by leaf, and the one device they
    live on (None: a leaf spans devices, the program is left to the jit)."""

    tree: str
    leaves: Tuple[Tuple, ...]
    device: Any


def signature(leaves, tree) -> Signature:
    import jax

    rows, devices = [], set()
    for x in leaves:
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            devices |= set(sharding.device_set)
        rows.append((tuple(x.shape), str(x.dtype),
                     bool(getattr(x, "weak_type", False)),
                     None if sharding is None else
                     (type(sharding).__name__, sharding.memory_kind)))
    if not devices:
        devices = {jax.devices()[0]}
    device = devices.pop() if len(devices) == 1 else None
    return Signature(str(tree), tuple(rows), device)


def _runtime_version(dev) -> str:
    """The PJRT client's own version string (on a chip: libtpu's build)."""
    return dev.client.platform_version


def program_key(label: str, cache_key: Any, sig: Signature) -> Dict[str, Any]:
    """Everything the executable depends on, as a JSON-able dict (an entry
    keeps it beside the blob, so a miss can be explained by a diff)."""
    import jax
    import jaxlib
    from jax._src import config as jax_config

    dev = sig.device
    return {
        "source": source_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "platform_version": _runtime_version(dev),
        "device_kind": dev.device_kind,
        "device": [dev.process_index, dev.id],
        "label": label,
        "program": repr(cache_key),
        "tree": sig.tree,
        "leaves": [list(r) for r in sig.leaves],
        "donated": list(DONATED),
        "env": [list(kv) for kv in key_env()],
        "jax_config": repr(jax_config.trace_context()),
    }


def key_digest(key: Dict[str, Any]) -> str:
    return hashlib.sha256(
        json.dumps(key, sort_keys=True).encode()).hexdigest()


# ----------------------------------------------------------------------
# entries: MAGIC, one line of JSON (what `list` reads), then the pickle
# ----------------------------------------------------------------------


def entry_path(root: str, digest: str) -> str:
    return os.path.join(root, digest + SUFFIX)


def read_meta(path: str) -> Optional[Dict[str, Any]]:
    """An entry's first lines alone; None for a foreign or torn file."""
    try:
        with open(path, "rb") as f:
            if f.readline() != MAGIC:
                return None
            return json.loads(f.readline())
    except (OSError, ValueError):
        return None


def read_entry(path: str) -> Optional[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """(meta, body) of an entry; None where there is none to trust."""
    try:
        with open(path, "rb") as f:
            if f.readline() != MAGIC:
                return None
            meta = json.loads(f.readline())
            body = pickle.load(f)
        return meta, body
    except Exception:  # a torn or foreign file raises whatever it likes
        return None


def write_entry(root: str, digest: str, meta: Dict[str, Any],
                body: Dict[str, Any]) -> int:
    """Write one entry atomically, then hold the directory to MAX_BYTES."""
    os.makedirs(root, exist_ok=True)
    blob = pickle.dumps(body, protocol=pickle.HIGHEST_PROTOCOL)
    meta = dict(meta, bytes=len(blob))
    fd, tmp = tempfile.mkstemp(dir=root, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(MAGIC)
            f.write(json.dumps(meta, sort_keys=True).encode() + b"\n")
            f.write(blob)
        os.replace(tmp, entry_path(root, digest))
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    evict(root, MAX_BYTES)
    return len(blob)


def entries(root: str) -> List[Tuple[str, int, float]]:
    """(path, bytes, mtime) of every entry, least recently used first."""
    out = []
    try:
        names = os.listdir(root)
    except OSError:
        return out
    for name in names:
        if not name.endswith(SUFFIX):
            continue
        path = os.path.join(root, name)
        try:
            st = os.stat(path)
        except OSError:
            continue
        out.append((path, st.st_size, st.st_mtime))
    return sorted(out, key=lambda e: e[2])


def evict(root: str, max_bytes: int) -> int:
    """Unlink least recently used entries until the rest fit; -> removed."""
    found = entries(root)
    total, removed = sum(e[1] for e in found), 0
    for path, size, _ in found:
        if total <= max_bytes:
            break
        try:
            os.unlink(path)
        except OSError:
            continue
        total -= size
        removed += 1
    return removed


# ----------------------------------------------------------------------
# the program `_jit_step` hands out where the store is on
# ----------------------------------------------------------------------


def text_digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def wrap(label: str, jit_fn: Callable, cache_key: Any,
         recipe: Optional[Tuple] = None) -> Callable:
    """`jit_fn` itself where the store is off, else a `StoredProgram` over
    it.  (A program over a mesh never gets here: `_jit_step` has no key for
    it.)"""
    if _dir is None:
        return jit_fn
    return StoredProgram(label, jit_fn, cache_key, recipe, _dir)


class StoredProgram:
    """A step program whose executables come from the store.

    One `jax.jit` serves every signature it is called with (decode with or
    without a mask: another tree).  So does this: a call's argument TREE and
    the device its first leaf lives on pick the variant, resolved once:
    loaded from the store, or compiled and written, or the jit itself (a
    leaf over several devices; no key).  A `Compiled` checks shapes and
    dtypes itself before it runs; a call it refuses turns its variant into
    the jit, counted (`store_fallbacks`)."""

    def __init__(self, label: str, jit_fn: Callable, cache_key: Any,
                 recipe: Optional[Tuple], root: str):
        self.label, self.jit, self.root = label, jit_fn, root
        self.cache_key, self.recipe = cache_key, recipe
        self.variants: Dict[Any, Callable] = {}
        self.__wrapped__ = jit_fn
        self.__name__ = getattr(jit_fn, "__name__", label)
        from jax import tree_util  # (lazily: compile_log imports this module)

        self._flatten, self._leaves = (
            tree_util.tree_flatten, tree_util.tree_leaves)

    def __call__(self, *args):
        leaves, tree = self._flatten(args)
        which = (tree, getattr(leaves[0], "sharding", None))
        fn = self.variants.get(which)
        if fn is None:
            fn = self.variants[which] = self._resolve(args, leaves, tree)
        if fn is self.jit:
            return fn(*args)
        try:
            return fn(*args)
        except (TypeError, ValueError) as e:
            # the Compiled's own check of the call, ahead of any execution
            # (jax stages.Compiled.call): the donated pools are untouched
            if any(getattr(x, "is_deleted", bool)() for x in self._leaves(
                    [args[i] for i in DONATED if i < len(args)])):
                raise
            logger.warning("program store: %s called with other arguments "
                           "than it was compiled for; left to the jit (%s)",
                           self.label, str(e).splitlines()[0])
            _count("store_fallbacks")
            self.variants[which] = self.jit
            return self.jit(*args)

    def _resolve(self, args, leaves, tree) -> Callable:
        try:
            sig = signature(leaves, tree)
            if sig.device is None:
                return self.jit
            key = program_key(self.label, self.cache_key, sig)
            digest = key_digest(key)
        except Exception:
            logger.warning("program store: no key for %s; left to the jit",
                           self.label, exc_info=True)
            return self.jit
        path = entry_path(self.root, digest)
        loaded = self._load(path, sig.device)
        if loaded is not None:
            return loaded
        return self._compile(args, key, digest)

    def _load(self, path: str, device) -> Optional[Callable]:
        from jax.experimental import serialize_executable

        from . import compile_log

        if not os.path.exists(path):
            return None
        t0 = time.monotonic()
        try:
            found = read_entry(path)
            if found is None:
                raise ValueError("not an entry of this store, or torn")
            body = found[1]
            compiled = serialize_executable.deserialize_and_load(
                body["payload"], body["in_tree"], body["out_tree"],
                backend=device.client, execution_devices=[device])
        except Exception as e:
            # another libtpu, a torn file: a miss, written over by _compile
            logger.warning("program store: %s did not load (%s: %s); "
                           "compiling", os.path.basename(path),
                           type(e).__name__, str(e)[:200])
            return None
        dt = time.monotonic() - t0
        try:
            os.utime(path)  # least recently USED goes first
        except OSError:
            pass
        _count("store_hits", dt)
        compile_log.record_store_load(self.label, dt)
        return compiled

    def _compile(self, args, key, digest) -> Callable:
        """The cold path: lower and compile as the jit would (the persistent
        cache and the observatory's listeners see it as before), then write
        the entry.  A failed write is logged once and the boot goes on."""
        global _write_failed
        from jax.experimental import serialize_executable

        lowered = self.jit.lower(*args)
        compiled = lowered.compile()
        _count("store_misses")
        t0 = time.monotonic()
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            text = lowered.as_text()
            body = {
                "payload": payload, "in_tree": in_tree, "out_tree": out_tree,
                # for `verify`: how to trace the program again, and the
                # text it lowered to (a few hundredths of the blob)
                "recipe": self.recipe, "lowered": zlib.compress(text.encode()),
            }
            meta = {"label": self.label, "created": round(time.time(), 3),
                    "process": _PROCESS, "lowered": text_digest(text),
                    "key": key}
            size = write_entry(self.root, digest, meta, body)
            logger.info("program store: wrote %s (%s, %.1f MB, %.2fs)",
                        digest[:12], self.label, size / 1e6,
                        time.monotonic() - t0)
        except Exception as e:
            if not _write_failed:
                _write_failed = True
                logger.warning(
                    "program store: cannot write under %s (%s: %s); booting "
                    "on without it", self.root, type(e).__name__,
                    str(e)[:200])
        return compiled


# ----------------------------------------------------------------------
# offline: scripts/program_store.py
# ----------------------------------------------------------------------


def verify_entry(path: str) -> Tuple[str, str]:
    """(verdict, detail) of one entry against THIS tree, with no chip: an
    entry this process would itself look up (same sources, environment, jax
    configuration and platform) is traced and lowered again from its recipe
    and the text's digest compared with the one kept beside the blob.
    `equal` is the proof that its key held everything the trace read;
    `differs` is a key that is missing an ingredient.  The script verifies
    the entries ONE server process wrote in a process of their own, in the
    order they were made: jax keeps a Pallas kernel's traced body, scopes and
    all, for the next program that calls it (a decode program's Mosaic
    payload reads otherwise when no prefill program was lowered before it,
    and otherwise again after another model's), and `jax.clear_caches()` does
    not reach it."""
    import jax

    from . import step_programs

    found = read_entry(path)
    if found is None:
        return "unreadable", "not an entry of this store, or torn"
    meta, body = found
    key = meta["key"]
    if key["platform"] != jax.default_backend():
        return "skipped", f"made on {key['platform']}"
    sig = Signature(key["tree"], tuple(tuple(r) for r in key["leaves"]),
                    jax.devices()[0])
    mine = program_key(key["label"], None, sig)
    stale = [k for k in ("source", "jax", "jaxlib", "platform_version",
                         "env", "jax_config") if mine[k] != key[k]]
    if stale:
        return "stale", "made under another " + ", ".join(stale)
    if not body.get("recipe"):
        return "skipped", "no recipe"
    make, cfg, ps, extra = body["recipe"]
    fn = getattr(step_programs, make)(cfg, None, ps, *extra)
    fn.__name__ = fn.__qualname__ = step_programs.program_name(key["label"])
    specs = [jax.ShapeDtypeStruct(tuple(shape), dtype, weak_type=weak)
             for shape, dtype, weak, _ in key["leaves"]]
    args, kwargs = body["in_tree"].unflatten(specs)
    text = jax.jit(fn, donate_argnums=DONATED).lower(*args, **kwargs).as_text()
    if text_digest(text) == meta["lowered"]:
        return "equal", meta["lowered"][:12]
    stored = zlib.decompress(body["lowered"]).decode()
    return "differs", _first_difference(stored, text)


def _first_difference(stored: str, now: str, width: int = 120) -> str:
    """Where two lowered texts part: the line, and both sides around the
    first differing column."""
    a, b = stored.split("\n"), now.split("\n")
    for n, (x, y) in enumerate(zip(a, b)):
        if x != y:
            col = next((i for i, (c, d) in enumerate(zip(x, y)) if c != d),
                       min(len(x), len(y)))
            lo = max(0, col - width // 2)
            return (f"line {n + 1} col {col}: stored ...{x[lo:lo + width]}... "
                    f"now ...{y[lo:lo + width]}...")
    return f"{len(a)} lines stored, {len(b)} now"
