"""Compile plan + AOT serving bundles — the cold-start kill switch.

Reference surface: the deployment layer's ``save_inference_model`` /
``jit.save`` contract (paddle/fluid/inference — a serving process loads a
ready artifact instead of rebuilding programs). JAX-native equivalent,
split in three:

* **CompilePlan** — a declarative enumeration of every compiled program a
  :class:`~.decode_engine.BatchDecodeEngine` config implies: the chunked
  decode program plus one admission program per prompt-length bucket
  (``prompt_buckets``), each entry carrying its donate/static facts. The
  plan is the single seam the engine's formerly scattered program
  construction (lazy per-bucket ``jax.jit`` builds, prefix-HIT factories)
  now flows through: ``engine.warmup()`` walks it eagerly,
  ``save_bundle``/``load_bundle`` serialize it, ``health()`` reports it,
  and a future mesh-planning pass can rewrite it before anything
  compiles.
* **Fingerprint** — a sha256 over the plan's *facts* (model architecture,
  slots/len/chunk, KV layout + page geometry, quant scheme, mesh, jax/
  jaxlib/platform). Two engines with equal fingerprints compile
  interchangeable programs; a bundle is only loaded into an engine whose
  fingerprint matches its manifest.
* **Bundle** — a directory of AOT-serialized compiled executables
  (``jax.experimental.serialize_executable`` — the XLA executable itself,
  not StableHLO, so loading performs ZERO retrace and ZERO backend
  compile) plus ``manifest.json``. Argument/output pytree structures are
  NOT pickled into the bundle: they are reconstructed at load time from
  the live engine's own state templates (``_example_args`` /
  ``_out_template``), which sidesteps custom-pytree (QuantizedWeight)
  serialization entirely and is one more reason the fingerprint gate must
  pass first.

Commit discipline mirrors checkpoint format v3: bundles are written to a
staging directory and renamed into place, so a killed save leaves the
previous bundle intact or the path absent — never a half-written artifact.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Tuple

BUNDLE_FORMAT_VERSION = 1
MANIFEST_NAME = "manifest.json"

# program keys are strings so they double as bundle file names:
#   "decode"                 — the chunked multi-step decode program
#   "admit_p<bucket>"        — admission prefill at one prompt bucket
#   "admit_pfx<n>t<bucket>"  — prefix-HIT admission (n cached pages,
#                              tail bucket) — built on traffic, bundled
#                              when present
#   "draft_admit_p<bucket>"  — speculative: draft-model prompt prefill
#   "draft_k<K>"             — speculative: K greedy draft proposals
#   "verify_k<K>"            — speculative: one batched target verify
#                              over K+1 positions + masked accept/reject
_ADMIT_RE = re.compile(r"^admit_p(\d+)$")
_PREFIX_RE = re.compile(r"^admit_pfx(\d+)t(\d+)$")
_DRAFT_ADMIT_RE = re.compile(r"^draft_admit_p(\d+)$")
_DRAFT_RE = re.compile(r"^draft_k(\d+)$")
_VERIFY_RE = re.compile(r"^verify_k(\d+)$")


def decode_key() -> str:
    return "decode"


def admit_key(bucket: int) -> str:
    return f"admit_p{int(bucket)}"


def prefix_admit_key(n_pfx: int, tail_bucket: int) -> str:
    return f"admit_pfx{int(n_pfx)}t{int(tail_bucket)}"


def draft_admit_key(bucket: int) -> str:
    return f"draft_admit_p{int(bucket)}"


def draft_key(k: int) -> str:
    return f"draft_k{int(k)}"


def verify_key(k: int) -> str:
    return f"verify_k{int(k)}"


def parse_key(key: str) -> Tuple[str, Dict[str, int]]:
    """(kind, info) for a program key; raises ValueError on garbage so a
    tampered bundle entry fails loud instead of building nonsense."""
    if key == "decode":
        return "decode", {}
    m = _ADMIT_RE.match(key)
    if m:
        return "admit", {"bucket": int(m.group(1))}
    m = _PREFIX_RE.match(key)
    if m:
        return "prefix", {"n_pfx": int(m.group(1)),
                          "tail_bucket": int(m.group(2))}
    m = _DRAFT_ADMIT_RE.match(key)
    if m:
        return "draft_admit", {"bucket": int(m.group(1))}
    m = _DRAFT_RE.match(key)
    if m:
        return "draft", {"k": int(m.group(1))}
    m = _VERIFY_RE.match(key)
    if m:
        return "verify", {"k": int(m.group(1))}
    raise ValueError(f"unrecognized compile-plan program key {key!r}")


def prompt_buckets(max_len: int, q: int = 128) -> List[int]:
    """Every admission bucket the engine can compile: multiples of ``q``
    below ``max_len``, then ``max_len`` itself (the engine clips
    ``_bucket(plen)`` to ``max_len``, so the top bucket is always L)."""
    buckets = []
    b = q
    while b < max_len:
        buckets.append(b)
        b += q
    buckets.append(int(max_len))
    return buckets


class PlanEntry:
    """One compiled program the plan implies."""

    __slots__ = ("key", "kind", "meta")

    def __init__(self, key: str, kind: str, meta: Optional[Dict] = None):
        self.key = key
        self.kind = kind
        self.meta = dict(meta or {})

    def describe(self) -> Dict[str, object]:
        return {"key": self.key, "kind": self.kind, **self.meta}

    def __repr__(self):
        return f"PlanEntry({self.key})"


class CompilePlan:
    """Declarative program inventory for one engine config + the facts
    that make its compiled programs exchangeable (the fingerprint)."""

    def __init__(self, entries: List[PlanEntry], facts: Dict[str, object]):
        self.entries = list(entries)
        self.facts = facts
        self._fingerprint: Optional[str] = None

    @classmethod
    def for_engine(cls, engine) -> "CompilePlan":
        """Enumerate what ``engine``'s config implies: one decode program
        and one admission program per prompt bucket. Prefix-HIT programs
        are traffic-shaped (cached pages x tail bucket) so they are not
        pre-enumerated — once built they ride warmup state and bundles
        like any other program."""
        import jax
        import jaxlib

        cfg = engine.cfg
        model = {k: v for k, v in sorted(vars(cfg).items())
                 if isinstance(v, (int, float, str, bool, type(None)))}
        facts: Dict[str, object] = {
            "model": model,
            "max_slots": engine.S,
            "max_len": engine.L,
            "chunk": engine.chunk,
            "kv_layout": engine.kv_layout,
            "page_size": engine.page_size,
            "num_pages": (engine.pool.num_pages
                          if engine.pool is not None else 0),
            "prefix_cache": bool(engine.prefix_enabled),
            "quant": engine.quant or "off",
            "quant_group_size": (engine.quant_meta.get("group_size", -1)
                                 if engine.quant else -1),
            # int8 KV pages change every program that touches the pool
            # (admission quantize-scatter, decode dequant, verify) AND the
            # cache pytree's treedef — a bundle built under the other
            # scheme must be rejected at load, not deserialized into the
            # wrong structure. The host spill tier is deliberately NOT a
            # fact: it never changes a compiled program.
            "kv_quant": getattr(engine, "kv_quant", None) or "off",
            "mesh": (engine.plan.describe()
                     if engine.plan is not None else None),
            # speculative decoding: draft arch + quant + k make the
            # draft/verify programs (and the decode path's semantics)
            # exchangeable — a draft-model swap MUST change the
            # fingerprint so a stale bundle falls back loudly instead of
            # serving another draft's executables
            "spec": (engine.spec.facts()
                     if getattr(engine, "spec", None) is not None else None),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            "n_devices": jax.device_count(),
        }
        spec_on = getattr(engine, "spec", None) is not None
        entries = []
        if not spec_on:
            # a speculative engine routes EVERY chunk through the
            # draft/verify programs, so the plain chunked-decode scan —
            # the single most expensive compile in the plan — would be
            # dead weight in warmup and bundles
            entries.append(PlanEntry(decode_key(), "decode",
                                     {"slots": engine.S,
                                      "chunk": engine.chunk}))
        for b in prompt_buckets(engine.L):
            entries.append(PlanEntry(admit_key(b), "admit", {"bucket": b}))
        if spec_on:
            k = engine.spec.k
            for b in prompt_buckets(engine.L):
                entries.append(PlanEntry(draft_admit_key(b), "draft_admit",
                                         {"bucket": b}))
            entries.append(PlanEntry(draft_key(k), "draft", {"k": k}))
            entries.append(PlanEntry(verify_key(k), "verify", {"k": k}))
        return cls(entries, facts)

    def keys(self) -> List[str]:
        return [e.key for e in self.entries]

    def fingerprint(self) -> str:
        """Stable content hash of the facts — NOT of the entry list, so a
        bundle carrying extra traffic-built programs (prefix variants)
        still matches an engine whose static plan lacks them."""
        if self._fingerprint is None:
            blob = json.dumps(self.facts, sort_keys=True, default=str)
            self._fingerprint = hashlib.sha256(blob.encode()).hexdigest()
        return self._fingerprint

    def describe(self) -> Dict[str, object]:
        """The ``health()``/``/healthz`` compile-plan block."""
        return {
            "entries": len(self.entries),
            "keys": self.keys(),
            "fingerprint": self.fingerprint()[:16],
        }


class BundleMismatchError(RuntimeError):
    """A bundle exists but cannot serve this engine: fingerprint/platform/
    version/integrity mismatch. Carries the differing fields so the
    fallback log says WHY the artifact was rejected."""

    def __init__(self, msg: str, mismatches: Optional[List[str]] = None):
        super().__init__(msg)
        self.mismatches = list(mismatches or [])


def _facts_diff(a: Dict, b: Dict) -> List[str]:
    keys = sorted(set(a) | set(b))
    return [k for k in keys if a.get(k) != b.get(k)]


# -- bundle version identity (stdlib — the fleet deploy pipeline reads
#    these without importing jax) ------------------------------------------

def bundle_version_id(fingerprint: str, created_unix: float) -> str:
    """Short human-safe version id: enough fingerprint to name the
    compiled-program identity, plus the save second so two rebuilds of
    the SAME facts are still tellable apart in a rollout/rollback log."""
    return f"{str(fingerprint)[:12]}@{int(created_unix)}"


def read_manifest(path: str) -> Dict[str, object]:
    """Load a bundle's manifest (stdlib, no jax). Older bundles saved
    before the ``version`` field get one derived from their fingerprint +
    timestamp, so every manifest this returns carries a version identity
    the rollback machinery can key on."""
    with open(os.path.join(path, MANIFEST_NAME)) as f:
        manifest = json.load(f)
    if not isinstance(manifest, dict):
        raise ValueError(f"{path}: manifest is not a JSON object")
    if not manifest.get("version"):
        manifest["version"] = bundle_version_id(
            manifest.get("fingerprint", "?"),
            manifest.get("created_unix", 0) or 0)
    return manifest


def validate_bundle(path: str) -> Dict[str, object]:
    """Pre-flight a candidate bundle for the fleet deploy pipeline —
    cheap, stdlib-only, BEFORE any replica is touched: the manifest
    parses, the format version is supported, a fingerprint is present,
    and every entry's payload exists and matches its sha256. Returns the
    manifest (with ``version``). Raises :class:`BundleMismatchError` /
    OSError / ValueError on any problem; whether the fingerprint matches
    a given ENGINE is still decided at load time per replica."""
    manifest = read_manifest(path)
    if manifest.get("format_version") != BUNDLE_FORMAT_VERSION:
        raise BundleMismatchError(
            f"bundle format {manifest.get('format_version')!r} != "
            f"{BUNDLE_FORMAT_VERSION}", ["format_version"])
    if not manifest.get("fingerprint"):
        raise BundleMismatchError("bundle manifest carries no fingerprint",
                                  ["fingerprint"])
    for entry in manifest.get("entries", []):
        key = entry.get("key", "?")
        parse_key(key)
        fpath = os.path.join(path, entry.get("file", ""))
        with open(fpath, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        if digest != entry.get("sha256"):
            raise BundleMismatchError(
                f"bundle entry {key}: payload sha256 mismatch "
                "(corrupted or tampered artifact)", [key])
    return manifest


def save_bundle(engine, path: str,
                keys: Optional[List[str]] = None) -> Dict[str, object]:
    """Serialize the engine's compiled programs (every plan entry plus any
    traffic-built extras, e.g. prefix-HIT variants) into a bundle
    directory at ``path``. Programs not yet compiled are AOT-compiled
    here — saving from a warmed engine serializes the exact executables
    it serves with. Returns the manifest. Atomic: staging dir + rename."""
    import jax
    import jaxlib
    from jax.experimental import serialize_executable as _se

    if keys is None:
        plan_keys = engine.compile_plan.keys()
        extra = sorted(k for k in engine._programs if k not in plan_keys)
        keys = plan_keys + extra
    staging = f"{path}.staging.{os.getpid()}"
    shutil.rmtree(staging, ignore_errors=True)
    os.makedirs(staging)
    t0 = time.perf_counter()
    entries = []
    try:
        for key in keys:
            parse_key(key)                     # refuse unsaveable keys early
            fn = engine._programs.get(key)
            if fn is None or hasattr(fn, "lower"):
                # still a lazy jit (or never built): AOT-compile now and
                # keep the Compiled so the live engine serves what it saved
                jit_fn = fn if fn is not None else engine._build_program(key)
                fn = jit_fn.lower(*engine._example_args(key)).compile()
                engine._programs[key] = fn
                engine._warmed.add(key)
            payload, in_tree, out_tree = _se.serialize(fn)
            # a payload that cannot load back is worse than no bundle (it
            # fails at RESTART, the moment the bundle exists for): prove
            # the round trip now and let a failure raise. On the TPU client
            # it holds for executables that themselves came out of the
            # persistent cache too (PR 21, chip run). This jaxlib's CPU
            # client refuses to serialize an executable that has already
            # RUN a sort-by-comparator (UNIMPLEMENTED: `LessThan` is not
            # serializable) — every engine program samples through
            # lax.top_k — so a served CPU engine cannot save a bundle.
            _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=_execution_devices(engine))
            fname = f"{key}.xc"
            with open(os.path.join(staging, fname), "wb") as f:
                f.write(payload)
            entries.append({
                "key": key,
                "file": fname,
                "bytes": len(payload),
                "sha256": hashlib.sha256(payload).hexdigest(),
            })
        created = time.time()
        manifest = {
            "format_version": BUNDLE_FORMAT_VERSION,
            "created_unix": round(created, 3),
            "version": bundle_version_id(
                engine.compile_plan.fingerprint(), created),
            "fingerprint": engine.compile_plan.fingerprint(),
            "facts": engine.compile_plan.facts,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            "n_devices": jax.device_count(),
            "entries": entries,
        }
        with open(os.path.join(staging, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f, indent=1, default=str)
        # committed-or-absent (checkpoint v3 discipline): the only
        # non-atomic window is between removing an OLD bundle and the
        # rename; a failed commit (path occupied by a non-directory,
        # concurrent recreation) must not leak the staging dir either
        if os.path.isdir(path):
            shutil.rmtree(path)
        os.rename(staging, path)
    except BaseException:
        shutil.rmtree(staging, ignore_errors=True)
        raise
    manifest["save_wall_s"] = round(time.perf_counter() - t0, 3)
    return manifest


def _execution_devices(engine) -> list:
    """The devices the engine's programs run on, in assignment order: its
    plan's mesh, or the one device its state lives on. Left to its default,
    ``deserialize_and_load`` loads for EVERY device of the backend, and a
    one-chip program on a four-chip host (or on the tests' eight virtual
    CPU devices) then refuses its arguments: "expected 8 shards, got 1"."""
    if engine.plan is not None:
        return list(engine.plan.mesh.devices.flat)
    return list(engine.lens.devices())


def load_bundle(engine, path: str) -> Dict[str, object]:
    """Deserialize a bundle into the engine's program registry — zero
    retrace, zero backend compile. All-or-nothing: the registry is only
    touched after every entry loads and verifies. Raises
    :class:`BundleMismatchError` (or OSError/ValueError) on any problem;
    the engine's non-strict wrapper turns that into a logged fallback."""
    import jax
    import jaxlib
    from jax.experimental import serialize_executable as _se
    from jax.tree_util import tree_structure

    mpath = os.path.join(path, MANIFEST_NAME)
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("format_version") != BUNDLE_FORMAT_VERSION:
        raise BundleMismatchError(
            f"bundle format {manifest.get('format_version')!r} != "
            f"{BUNDLE_FORMAT_VERSION}", ["format_version"])
    env_mismatch = []
    if manifest.get("platform") != jax.default_backend():
        env_mismatch.append(
            f"platform {manifest.get('platform')}!={jax.default_backend()}")
    if manifest.get("jaxlib") != jaxlib.__version__:
        env_mismatch.append(
            f"jaxlib {manifest.get('jaxlib')}!={jaxlib.__version__}")
    if env_mismatch:
        # serialized executables are jaxlib+platform artifacts; a partial
        # deserialize crash is exactly what this check pre-empts
        raise BundleMismatchError(
            "bundle was built for a different runtime: "
            + ", ".join(env_mismatch), env_mismatch)
    fp = engine.compile_plan.fingerprint()
    if manifest.get("fingerprint") != fp:
        diff = _facts_diff(manifest.get("facts") or {},
                           engine.compile_plan.facts)
        raise BundleMismatchError(
            f"bundle fingerprint {str(manifest.get('fingerprint'))[:16]} != "
            f"engine {fp[:16]} (differing facts: {', '.join(diff) or '?'})",
            diff)
    loaded: Dict[str, object] = {}
    for entry in manifest.get("entries", []):
        key = entry["key"]
        parse_key(key)                          # garbage keys fail loud
        fpath = os.path.join(path, entry["file"])
        with open(fpath, "rb") as f:
            payload = f.read()
        digest = hashlib.sha256(payload).hexdigest()
        if digest != entry.get("sha256"):
            raise BundleMismatchError(
                f"bundle entry {key}: payload sha256 mismatch "
                "(corrupted or tampered artifact)", [key])
        # pytree structures come from the LIVE engine, not the disk: the
        # fingerprint gate already proved both sides build identical arg
        # trees, and this keeps custom pytree leaves (QuantizedWeight)
        # out of the serialization format entirely
        in_tree = tree_structure((engine._example_args(key), {}))
        out_tree = tree_structure(engine._out_template(key))
        try:
            loaded[key] = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=_execution_devices(engine))
        except Exception as e:
            raise BundleMismatchError(
                f"bundle entry {key}: executable failed to deserialize "
                f"({type(e).__name__}: {e})", [key]) from e
    engine._programs.update(loaded)
    engine._warmed.update(loaded)
    return manifest
