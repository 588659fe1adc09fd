"""Executables of the wrapped programs, kept under a key that costs no trace
(docs/OBSERVABILITY.md "Program store").

jax's persistent compilation cache is keyed on the LOWERED module, so a warm
start pays the Python trace and the lowering of every program to learn a key,
and the cache saves the compile alone. This store is keyed on what a program
IS, so a hit costs the read and the load:

- **What a key holds** (:func:`key_text`): a hash of every source file of the
  ``dynamo_tpu`` package; the program's family, shape key and labels; the runner's account of what its
  programs close over (:class:`Context`: ``ModelSpec``, ``EngineConfig``,
  ``Backends``, the mesh); the first call's abstract signature (tree
  structure; an array's shape, dtype, weak type, sharding and whether it is
  committed; a Python scalar by its type); the jit's own arguments (the
  donation); jax, jaxlib and the PJRT plugin's versions, the platform, the
  device kind and ids; ``XLA_FLAGS``, ``LIBTPU_INIT_ARGS`` and every
  ``DTPU_*`` and ``JAX_*`` variable that does not name a directory; the
  scope vocabulary's version.
- **What a key cannot hold closes the store**: a function of a package module
  whose code comes from another file (a test's or a check's hook bound in
  place of ``model.select_topk``) is part of no hash, so a wrapper first
  called while one is bound has no store (:func:`foreign_code`).
- **An entry** is one file, ``<family>-<digest>.prog``, written under a
  temporary name and moved into place (several workers share a directory):
  deflated, a pickled header (the key's text, which names the format, jax
  and jaxlib; the family's cost estimate) and the serialized executable
  (``jax.experimental.serialize_executable``). Anything about it that does
  not hold is a *reject*: the file goes, the caller builds the program.

Who may use it is not decided here: perf.py opens a :class:`Context` for a
runner built inside a launcher's start, and gives none to anyone else.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import sys
import sysconfig
import threading
import types
import zlib

import jax
import jaxlib

#: Bump when an entry's layout or what a key holds changes.
FORMAT = 1
#: An entry is deflated at the fastest level: a TPU executable shrinks to a
#: quarter (170 -> some 40 MB for the Qwen cell's 19 programs; jax's own cache
#: compresses too), which a shared or evicted directory feels at every
#: start and a load pays with a few tenths of a second.
_DEFLATE_LEVEL = 1
#: The store's place inside perf.compile_cache_dir().
SUBDIR = "programs"
SUFFIX = ".prog"

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PACKAGE = os.path.basename(_PACKAGE_DIR)
#: Compiled and cached files beside the sources: no part of what a program is.
_NOT_SOURCE = (".pyc", ".pyo", ".so")
#: Where installed code lies: the standard library and the site packages.
_LIBRARY_DIRS = tuple(sorted({
    os.path.abspath(path) + os.sep
    for name, path in sysconfig.get_paths().items()
    if name in ("stdlib", "platstdlib", "purelib", "platlib")}))

_source_lock = threading.Lock()
_source_digest: str | None = None


def source_digest() -> str:
    """One hash over every source file of the package (relative name and
    bytes), computed once a process: a changed line anywhere is a miss,
    and two trees never share an entry."""
    global _source_digest
    with _source_lock:
        if _source_digest is None:
            h = hashlib.sha256()
            for root, dirs, files in os.walk(_PACKAGE_DIR):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                for name in sorted(files):
                    if name.endswith(_NOT_SOURCE):
                        continue
                    path = os.path.join(root, name)
                    h.update(os.path.relpath(path, _PACKAGE_DIR).encode())
                    with open(path, "rb") as fh:
                        h.update(fh.read())
            _source_digest = h.hexdigest()
        return _source_digest


def _package_modules() -> list:
    return [mod for name, mod in sorted(sys.modules.items())
            if mod is not None and (name == _PACKAGE
                                    or name.startswith(_PACKAGE + "."))]


def _code_file(obj) -> str | None:
    """The file of the Python code behind ``obj`` (a function, or what a
    decorator such as ``jax.jit`` wraps); None for anything else."""
    obj = getattr(obj, "__wrapped__", obj)
    code = getattr(obj, "__code__", None)
    return getattr(code, "co_filename", None)


def foreign_code() -> list[str]:
    """``module.name`` of every function bound in a loaded package module,
    or in a class defined there, whose code is a file of neither the
    package nor an installed library: a hook that no source hash sees."""
    found = []
    for mod in _package_modules():
        spaces = [(mod.__name__, vars(mod))]
        spaces += [(f"{mod.__name__}.{name}", vars(obj))
                   for name, obj in vars(mod).items()
                   if isinstance(obj, type) and obj.__module__ == mod.__name__]
        for where, space in spaces:
            for name, obj in list(space.items()):
                if isinstance(obj, (staticmethod, classmethod)):
                    obj = obj.__func__
                if not isinstance(obj, types.FunctionType) and not hasattr(
                        obj, "__wrapped__"):
                    continue
                path = _code_file(obj)
                if (path and not path.startswith("<")   # a dataclass's own
                        and not os.path.abspath(path).startswith(
                            (_PACKAGE_DIR + os.sep, *_LIBRARY_DIRS))):
                    found.append(f"{where}.{name}")
    return found


def environment() -> list[str]:
    """The variables that may reach a compiler or a trace. One that names a
    directory says where files go, not what a program is."""
    env = os.environ
    return [f"{k}={env[k]}" for k in sorted(env)
            if k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
            or (k.startswith(("DTPU_", "JAX_")) and not k.endswith("_DIR"))]


def _leaf_text(x) -> str:
    if isinstance(x, jax.Array):
        sharding = x.sharding
        return (f"{x.shape}{x.dtype}w{int(bool(getattr(x, 'weak_type', 0)))}"
                f"c{int(x.committed)} {sharding!r}"
                f"@{sorted(d.id for d in sharding.device_set)}")
    if hasattr(x, "shape") and hasattr(x, "dtype"):    # a numpy array
        return f"host{tuple(x.shape)}{x.dtype}"
    return type(x).__name__                 # a Python scalar: never its value


def signature_text(args, kwargs) -> str:
    """A call's abstract signature: the tree's structure and each leaf
    (_leaf_text)."""
    leaves, tree = jax.tree.flatten((args, kwargs))
    return f"{tree}\n" + "\n".join(_leaf_text(x) for x in leaves)


class Context:
    """What a runner tells the store of the programs it builds: the text of
    everything they close over, the devices they run on (in the mesh's
    order) and the directory."""

    __slots__ = ("text", "devices", "directory")

    def __init__(self, text: str, devices, directory: str):
        self.text = text
        self.devices = tuple(devices)
        self.directory = directory

    def runtime_text(self) -> str:
        first = self.devices[0]
        return "\n".join([
            f"jax={jax.__version__} jaxlib={jaxlib.__version__}",
            f"plugin={first.client.platform_version}",
            f"platform={first.platform} kind={first.device_kind}",
            f"devices={[d.id for d in self.devices]}"])


def key_text(context: Context, program: str, key, labels: dict,
             jit_kwargs: dict, args, kwargs, scopes_version: int) -> str:
    """Everything that decides which executable a wrapper's first call
    needs, as text (the module's docstring lists it)."""
    return "\n".join([
        f"format={FORMAT} scopes={scopes_version}",
        f"source={source_digest()}",
        f"program={program} key={key!r}",
        f"labels={sorted(labels.items())!r}",
        f"jit={sorted(jit_kwargs.items())!r}",
        context.runtime_text(),
        "-- context", context.text,
        "-- signature", signature_text(args, kwargs),
        "-- environment", *environment()])


class Reject(Exception):
    """An entry that is there and cannot be used; ``str`` is the reason."""


class Entry:
    """One program's place in the store."""

    __slots__ = ("path", "text", "devices")

    def __init__(self, context: Context, program: str, text: str):
        digest = hashlib.sha256(text.encode()).hexdigest()[:40]
        self.path = os.path.join(context.directory,
                                 f"{program}-{digest}{SUFFIX}")
        self.text = text
        self.devices = context.devices

    def load(self):
        """(the loaded ``jax.stages.Compiled``, the header) of a hit, None
        where there is no entry; :class:`Reject` for one that does not
        unpickle, was written for another key (the key's text names the
        format, jax and jaxlib), or that the runtime does not load (the
        file is gone by then)."""
        from jax.experimental import serialize_executable
        try:
            with open(self.path, "rb") as fh:
                blob = fh.read()
        except FileNotFoundError:
            return None
        except OSError as exc:          # a directory nobody may read
            raise Reject(f"unreadable: {type(exc).__name__}") from exc
        try:
            header, payload, in_tree, out_tree = pickle.loads(
                zlib.decompress(blob))
            if header["key"] != self.text:
                raise Reject("written for another key: "
                             + header["key"].partition("\n")[0][:80])
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, backend=self.devices[0].client,
                execution_devices=list(self.devices))
        except Exception as exc:  # noqa: BLE001 — whatever a damaged entry raises
            self.delete()
            if isinstance(exc, Reject):
                raise
            raise Reject(f"{type(exc).__name__}: {exc}"[:200]) from exc
        return compiled, header

    def save(self, compiled, cost: dict | None) -> None:
        """Serialize ``compiled`` and move it into place. Raises what the
        serialization or the directory raises."""
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = zlib.compress(
            pickle.dumps(({"key": self.text, "cost": cost}, payload, in_tree,
                          out_tree), protocol=pickle.HIGHEST_PROTOCOL),
            _DEFLATE_LEVEL)
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        tmp = f"{self.path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            with open(tmp, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, self.path)
        finally:
            with contextlib.suppress(OSError):  # moved into place, or never made
                os.unlink(tmp)

    def delete(self) -> None:
        with contextlib.suppress(OSError):
            os.unlink(self.path)
