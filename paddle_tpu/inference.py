"""Inference API + AOT-compiled export + the verified program cache.

Reference: python/paddle/v2/inference.py:9,93 (Inference wrapping a
GradientMachine in test mode; module-level `infer(output_layer=...,
input=...)`) and the C-API's merged-model deployment flow
(capi/gradient_machine.h:52, trainer/MergeModel.cpp). The runner itself
is trainer.Inferencer; this module adds the v2-style front door and the
TPU-native deployment artifacts:

- `export_compiled` serializes the jit-compiled forward as a portable
  StableHLO blob via jax.export — the analogue of shipping the merged
  binary to the pure-C runtime — and `load_compiled` runs it without
  the model-building code present.
- `store_verified` / `load_verified`: the **verified AOT program
  cache** (ISSUE 16). The stock persistent XLA compilation cache
  checks nothing it reloads (an earlier runtime was seen handing back
  corrupt executables), so the only trustworthy fast-boot path is one
  we verify ourselves: every cache entry carries sha256 digests over
  all of its files, the compiled
  program's HLO text, and a policy audited by `analysis/hlo_audit` —
  a replica may only boot from an entry whose digests match AND whose
  HLO passes the audit gate. Entries are published atomically (write
  to a temp dir, rename), so a writer SIGKILLed mid-store can never
  leave a half-visible entry.
"""

from __future__ import annotations

import logging

from paddle_tpu.core.arg import Arg
from paddle_tpu.trainer.trainer import Inferencer

Inference = Inferencer  # v2 name
logger = logging.getLogger(__name__)

__all__ = ["Inference", "Inferencer", "infer", "export_compiled",
           "load_compiled", "CompiledArtifactError", "VerifiedCacheError",
           "store_verified", "load_verified", "has_verified",
           "CACHE_META_SCHEMA"]


class CompiledArtifactError(ValueError):
    """Typed envelope failure for export_compiled artifacts. `reason`
    is one of: truncated, corrupt, version, no_envelope, deserialize.
    Subclasses ValueError so pre-existing `except ValueError` handlers
    keep catching it."""

    def __init__(self, source: str, reason: str, detail: str):
        super().__init__(
            f"compiled StableHLO artifact {source!r} is {reason}: "
            f"{detail}"
        )
        self.source = source
        self.reason = reason


class VerifiedCacheError(RuntimeError):
    """The verified AOT cache refused an entry at boot. `reason` is
    one of: missing, meta, digest, audit."""

    def __init__(self, reason: str, detail: str):
        super().__init__(f"verified cache refused ({reason}): {detail}")
        self.reason = reason


_ARG_SERIALIZATION_REGISTERED = False


def _register_arg_serialization():
    """jax.export needs (de)serializers for custom pytree nodes; Arg is
    a register_dataclass pytree, so auxdata is its static field tuple."""
    global _ARG_SERIALIZATION_REGISTERED
    if _ARG_SERIALIZATION_REGISTERED:
        return
    import json

    from jax import export as jexport

    try:
        jexport.register_pytree_node_serialization(
            Arg,
            serialized_name="paddle_tpu.core.arg.Arg",
            serialize_auxdata=lambda aux: json.dumps(aux).encode(),
            deserialize_auxdata=lambda b: tuple(json.loads(b.decode())),
        )
    except ValueError:
        pass  # already registered in this process
    _ARG_SERIALIZATION_REGISTERED = True


# export envelope: magic + version byte + sha256(payload) + payload.
# The digest lets load_compiled reject a torn or bit-flipped artifact
# with a typed CompiledArtifactError BEFORE the bytes reach XLA's
# deserializer (whose failure mode on corrupt input ranges from
# cryptic to process-fatal). The explicit version byte (ISSUE 16)
# lets the envelope itself evolve without a magic collision; v1
# envelopes (magic "PTPUXP1\x00", no version byte) still load.
_EXPORT_MAGIC = b"PTPUXP\x00"
_EXPORT_VERSION = 2
_LEGACY_MAGIC_V1 = b"PTPUXP1\x00"
_DIGEST_LEN = 32  # sha256


def _wrap_envelope(payload: bytes) -> bytes:
    import hashlib

    return (_EXPORT_MAGIC + bytes([_EXPORT_VERSION])
            + hashlib.sha256(payload).digest() + payload)


def _unwrap_envelope(blob: bytes, source: str,
                     require_envelope: bool = False):
    """Return the digest-verified payload, or raise
    CompiledArtifactError. Without `require_envelope`, a blob carrying
    no recognizable magic passes through untouched (pre-envelope
    artifact: best-effort)."""
    import hashlib

    blob = bytes(blob)
    if blob.startswith(_EXPORT_MAGIC):
        vpos = len(_EXPORT_MAGIC)
        if len(blob) < vpos + 1:
            raise CompiledArtifactError(
                source, "truncated", "envelope ends before the "
                "version byte — re-run export")
        version = blob[vpos]
        if version != _EXPORT_VERSION:
            raise CompiledArtifactError(
                source, "version",
                f"envelope version {version} != {_EXPORT_VERSION} "
                f"(or a corrupted version byte)")
        head = vpos + 1
    elif blob.startswith(_LEGACY_MAGIC_V1):
        head = len(_LEGACY_MAGIC_V1)
    else:
        if require_envelope:
            raise CompiledArtifactError(
                source, "corrupt",
                "no envelope magic found (corrupted header, or not "
                "an export_compiled artifact)")
        return blob  # pre-envelope artifact: best-effort load
    digest = blob[head:head + _DIGEST_LEN]
    payload = blob[head + _DIGEST_LEN:]
    if len(digest) < _DIGEST_LEN or not payload:
        raise CompiledArtifactError(
            source, "truncated",
            f"{len(blob)} bytes is shorter than the envelope header "
            f"— re-run export")
    if hashlib.sha256(payload).digest() != digest:
        raise CompiledArtifactError(
            source, "corrupt",
            f"checksum mismatch over {len(payload)} payload bytes "
            f"— re-run export")
    return payload


def export_compiled(inferencer: Inferencer, example_feed: dict) -> bytes:
    """Serialize the jitted forward specialized to `example_feed`'s
    shapes/dtypes as a checksummed StableHLO artifact (bytes)."""
    from jax import export as jexport

    _register_arg_serialization()
    exp = jexport.export(inferencer._fwd)(
        inferencer.params, inferencer.state, example_feed
    )
    return _wrap_envelope(exp.serialize())


def load_compiled(blob: bytes, source: str = "<compiled blob>",
                  require_envelope: bool = False):
    """Rehydrate an export_compiled artifact; returns
    fn(params, state, feed) -> {name: Arg}. Runs without the
    model-building code (config/layers) present. `source` names the
    artifact (e.g. its path) in error messages. A truncated or
    corrupted blob raises CompiledArtifactError (a ValueError) naming
    the artifact instead of crashing inside XLA; `require_envelope`
    additionally rejects blobs with no recognizable envelope (the
    verified-cache boot path sets it)."""
    from jax import export as jexport

    _register_arg_serialization()
    payload = _unwrap_envelope(blob, source,
                               require_envelope=require_envelope)
    try:
        exp = jexport.deserialize(payload)
    except Exception as e:
        raise CompiledArtifactError(
            source, "deserialize",
            f"payload failed to deserialize (truncated/corrupt or "
            f"version-skewed): {type(e).__name__}: {e}"
        ) from e
    return exp.call


# ---------------------------------------------------------------------
# verified AOT program cache (ISSUE 16)
#
# Entry layout (one directory per key under cache_dir):
#     <key>/program.exec     enveloped pickle of the serialized XLA
#                            executable (+ in/out tree defs) — the
#                            fast-boot path: deserialize_and_load,
#                            no trace/lower/compile
#     <key>/program.shlo     enveloped jax.export StableHLO — the
#                            portable fallback when the executable is
#                            version-skewed (recompiles on first call)
#     <key>/program.hlo.txt  the compiled program's HLO text — what
#                            the hlo_audit boot gate reads
#     <key>/meta.json        schema + sha256 per file + the audit
#                            policy the entry was stored under
#
# Publication is atomic: everything is written into a ".tmp-*" sibling
# and renamed into place, so a SIGKILL mid-store leaves only ignored
# temp garbage, never a half-visible entry.

CACHE_META_SCHEMA = "paddle-tpu-verified-cache/v1"
_CACHE_FILES = ("program.exec", "program.shlo", "program.hlo.txt")


def _sha256_file(path: str) -> str:
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def has_verified(cache_dir: str, key: str) -> bool:
    import os

    return os.path.exists(os.path.join(cache_dir, key, "meta.json"))


def store_verified(cache_dir: str, key: str, fn, example_args: tuple,
                   policy: dict = None) -> dict:
    """Compile `fn` (a jax-traceable callable over plain arrays)
    specialized to `example_args`, audit its HLO against `policy`
    (analysis/hlo_audit keys: host_transfer_budget, total_bytes_max,
    forbid_tt_materialization, ...), and publish the verified cache
    entry. Raises VerifiedCacheError("audit") — and publishes nothing
    — when the program already violates the policy at store time.
    Returns the entry's meta dict."""
    import json
    import os
    import pickle
    import shutil
    import tempfile
    import time

    import jax
    from jax import export as jexport
    from jax.experimental.serialize_executable import serialize

    from paddle_tpu.analysis import hlo_audit as _audit
    from paddle_tpu.core import compile_cache as _compile_cache

    policy = dict(policy or {})
    _register_arg_serialization()
    jitted = jax.jit(fn)
    with _compile_cache.bypassed():  # serialized below: compile fresh
        compiled = jitted.lower(*example_args).compile()
    hlo_text = compiled.as_text()
    exec_payload = pickle.dumps(serialize(compiled))
    shlo_payload = jexport.export(jitted)(*example_args).serialize()

    os.makedirs(cache_dir, exist_ok=True)
    tmp = tempfile.mkdtemp(prefix=f".tmp-{key}-", dir=cache_dir)
    try:
        with open(os.path.join(tmp, "program.exec"), "wb") as f:
            f.write(_wrap_envelope(exec_payload))
        with open(os.path.join(tmp, "program.shlo"), "wb") as f:
            f.write(_wrap_envelope(shlo_payload))
        hlo_path = os.path.join(tmp, "program.hlo.txt")
        with open(hlo_path, "w") as f:
            f.write(hlo_text)
        report = _audit.audit_capture(hlo_path, policy, report={})
        if not report["ok"]:
            bad = "; ".join(
                f"[{c['name']}] {c['detail']}"
                for c in report["checks"] if not c["ok"]
            )
            raise VerifiedCacheError(
                "audit", f"program for key {key!r} violates the "
                f"store policy: {bad}")
        meta = {
            "schema": CACHE_META_SCHEMA,
            "key": key,
            "created_unix": time.time(),
            "jax_version": jax.__version__,
            "num_devices": len(
                compiled.runtime_executable().local_devices()),
            "policy": policy,
            "files": {
                name: _sha256_file(os.path.join(tmp, name))
                for name in _CACHE_FILES
            },
            "n_instructions": report["n_instructions"],
            "total_bytes": report["total_bytes"],
            "example_args": [
                {"shape": list(getattr(a, "shape", ())),
                 "dtype": str(getattr(a, "dtype", ""))}
                for a in example_args
            ],
        }
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        final = os.path.join(cache_dir, key)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        return meta
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


class VerifiedProgram:
    """A booted cache entry: `call(*args)` runs the program; `via` is
    "exec" (deserialized executable, no compile) or "shlo" (portable
    export fallback — compiles on first call); `meta` is the entry's
    verified meta dict; `audit` the boot-gate report."""

    def __init__(self, call, via: str, meta: dict, audit: dict):
        self.call = call
        self.via = via
        self.meta = meta
        self.audit = audit

    def __call__(self, *args):
        return self.call(*args)


def load_verified(cache_dir: str, key: str,
                  policy: dict = None) -> VerifiedProgram:
    """Boot a program from the verified cache: digests first, then the
    hlo_audit policy gate, and only then XLA deserialization — the
    integrity check the stock persistent cache lacks. Extra `policy`
    keys tighten (merge over) the stored policy. Raises
    VerifiedCacheError before any unverified byte reaches XLA."""
    import json
    import os
    import pickle

    from paddle_tpu.analysis import hlo_audit as _audit

    entry = os.path.join(cache_dir, key)
    meta_path = os.path.join(entry, "meta.json")
    if not os.path.exists(meta_path):
        raise VerifiedCacheError(
            "missing", f"no entry for key {key!r} under {cache_dir}")
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        raise VerifiedCacheError(
            "meta", f"{meta_path}: unreadable ({e})") from e
    if meta.get("schema") != CACHE_META_SCHEMA:
        raise VerifiedCacheError(
            "meta", f"{meta_path}: schema {meta.get('schema')!r} != "
                    f"{CACHE_META_SCHEMA!r}")
    files = meta.get("files") or {}
    for name in _CACHE_FILES:
        path = os.path.join(entry, name)
        want = files.get(name)
        if not want or not os.path.exists(path):
            raise VerifiedCacheError(
                "digest", f"{name}: missing from the entry or its "
                          f"meta — torn or tampered entry")
        got = _sha256_file(path)
        if got != want:
            raise VerifiedCacheError(
                "digest", f"{name}: sha256 {got[:12]}… != recorded "
                          f"{want[:12]}… — corrupt or tampered entry")
    merged = dict(meta.get("policy") or {})
    if policy:
        merged.update(policy)
    hlo_path = os.path.join(entry, "program.hlo.txt")
    try:
        audit = _audit.audit_capture(hlo_path, merged, report={})
    except VerifiedCacheError:
        raise
    except BaseException as e:  # SystemExit from an unparseable capture
        raise VerifiedCacheError(
            "audit", f"audit could not run over {hlo_path}: "
                     f"{type(e).__name__}: {e}") from e
    if not audit["ok"]:
        bad = "; ".join(
            f"[{c['name']}] {c['detail']}"
            for c in audit["checks"] if not c["ok"]
        )
        raise VerifiedCacheError(
            "audit", f"entry {key!r} fails the boot policy gate: {bad}")
    # digests + audit passed: the bytes may now reach XLA. Fast path =
    # the serialized executable, loaded onto as many local devices as
    # it was compiled for (left to itself the loader spans EVERY local
    # device and the first call fails on its shard count). An
    # executable this runtime cannot load (another jaxlib, another
    # chip) falls back to the portable StableHLO export, which
    # recompiles on first call — logged and counted, never silent.
    import jax
    from jax.experimental.serialize_executable import (
        deserialize_and_load,
    )

    from paddle_tpu import obs as _obs

    exec_path = os.path.join(entry, "program.exec")
    with open(exec_path, "rb") as f:
        exec_blob = f.read()
    payload = _unwrap_envelope(exec_blob, exec_path,
                               require_envelope=True)
    devices = jax.local_devices()[:int(meta.get("num_devices", 1))]
    loads = _obs.get_registry().counter("inference.verified_loads")
    try:
        exe, in_tree, out_tree = pickle.loads(payload)
        compiled = deserialize_and_load(
            exe, in_tree, out_tree, execution_devices=devices)
    except Exception as e:  # noqa: BLE001 - any loader refusal
        logger.warning(
            "verified cache entry %r: executable did not load on %s "
            "(%s: %s); recompiling from the StableHLO export",
            key, devices, type(e).__name__, e)
        shlo_path = os.path.join(entry, "program.shlo")
        with open(shlo_path, "rb") as f:
            shlo_blob = f.read()
        call = load_compiled(shlo_blob, source=shlo_path,
                             require_envelope=True)
        loads.inc(via="shlo")
        return VerifiedProgram(call, "shlo", meta, audit)
    loads.inc(via="exec")
    return VerifiedProgram(compiled, "exec", meta, audit)


def infer(output=None, parameters=None, input=None, network=None,
          feeder=None):
    """One-shot inference (v2/inference.py:93 infer()). `input` is a
    feed dict of Args (or raw arrays, wrapped as dense Args; use
    `feeder` for sequence/ids packing). Returns one ndarray for a
    single output, else a list in `output` order."""
    outs = (
        None
        if output is None
        else [output] if isinstance(output, str) else list(output)
    )
    inf = Inferencer(network, parameters, outputs=outs)
    outs = inf.output_names
    feed = feeder(input) if feeder is not None else input
    feed = {
        k: (v if isinstance(v, Arg) else Arg(value=v))
        for k, v in feed.items()
    }
    res = inf.infer(feed)
    vals = [res[n] for n in outs]
    return vals[0] if len(vals) == 1 else vals
