"""Policy bundles: the pre-compiled rule-table artifact.

Behavioral reference: the reference's compile store / rule-table bundle
pipeline — `cerbos compilestore` serializes the built rule table + index
(internal/ruletable/index/marshal.go) and PDPs load it directly
(ruletable.RuleTableStore, internal/storage/hub/ruletable_bundle.go).

Two payload versions:

- v1: raw policy documents + schemas (sources; load recompiles).
- v2 adds ``compiled.bin``, the compiled policy IR (post YAML parse, CEL
  parse, import/variable resolution) — the analogue of the reference's
  serialized rule table. Loading it skips the parse+compile pipeline
  entirely: at the 900-doc classic corpus cold start drops ~2.0s → ~0.06s,
  at 8k docs ~12.6s → ~0.35s (round 4: msgpack container, the native
  linear node-pool decoder ``cerbos_native.decode_node_pool``, and
  ``util/gctune.build_phase`` GC pacing took the 8k decode+build from
  ~0.9s to ~0.35s on the CPU host of that round).

The compiled IR is a structured, versioned encoding
(``cerbos_tpu.bundle_codec``: tagged JSON over a closed node vocabulary) —
decoding is pure dataclass construction with NO code execution, so bundles
are safe to load from untrusted sources, exactly like the reference's
marshaled proto (index/marshal.go:20,240). An optional ``signing_key``
(config ``bundle.signingKey``) still provides supply-chain authenticity via
detached HMAC-SHA256 (the encrypted hub-bundle analogue,
storage/hub/ruletable_bundle.go:35): when configured, an IR whose signature
does not verify is ignored and the bundled sources recompile instead.
"""

from __future__ import annotations

import gzip
import hashlib
import hmac
import io
import json
import tarfile
import time
from dataclasses import dataclass
from typing import Optional

import yaml

from .bundle_codec import CodecError, decode_compiled, encode_compiled
from .policy import model
from .policy.parser import parse_policies
from .storage.store import Store, register_driver

BUNDLE_VERSION = 2
# bump when the compiled-IR shape changes; mismatched IR is ignored and the
# bundled sources recompile instead (ruletable.go:935-970's migration analogue)
COMPILER_VERSION = "cerbos-tpu-ir-2"
MANIFEST_NAME = "manifest.json"
COMPILED_NAME = "compiled.bin"


@dataclass
class BundleManifest:
    version: int
    created_at: str
    policy_count: int
    schema_count: int
    checksum: str  # sha256 over sorted entry digests
    compiler_version: str = ""
    compiled_checksum: str = ""  # sha256 of compiled.bin (corruption check only)
    compiled_signature: str = ""  # HMAC-SHA256(signing key, compiled.bin)


def build_bundle(
    store: Store,
    out_path: str,
    include_compiled: bool = True,
    signing_key: Optional[bytes] = None,
) -> BundleManifest:
    """Serialize a store's policies + schemas (and, by default, the compiled
    policy IR) into a bundle file. With ``signing_key`` the compiled IR gets
    an HMAC-SHA256 signature loaders can verify with the same key."""
    policies = store.get_all()
    schema_ids = store.list_schema_ids()

    entries: list[tuple[str, bytes]] = []
    for pol in policies:
        raw = getattr(store, "get_raw", lambda _fqn: None)(pol.fqn())
        if raw is None:
            raw = yaml.safe_dump(_policy_to_dict(pol), sort_keys=False)
        entries.append((f"policies/{hashlib.sha256(pol.fqn().encode()).hexdigest()[:16]}.yaml", raw.encode()))
    for sid in schema_ids:
        data = store.get_schema(sid)
        if data is not None:
            entries.append((f"_schemas/{sid}", data))

    digest = hashlib.sha256()
    for name, data in sorted(entries):
        digest.update(name.encode())
        digest.update(hashlib.sha256(data).digest())

    compiled_blob = b""
    if include_compiled:
        from .compile import compile_policy_set

        compiled = compile_policy_set(policies)
        compiled_blob = encode_compiled(compiled)

    manifest = BundleManifest(
        version=BUNDLE_VERSION,
        created_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        policy_count=len(policies),
        schema_count=len(schema_ids),
        checksum=digest.hexdigest(),
        compiler_version=COMPILER_VERSION if compiled_blob else "",
        compiled_checksum=hashlib.sha256(compiled_blob).hexdigest() if compiled_blob else "",
        compiled_signature=(
            hmac.new(signing_key, compiled_blob, hashlib.sha256).hexdigest()
            if compiled_blob and signing_key
            else ""
        ),
    )
    if compiled_blob:
        entries.append((COMPILED_NAME, compiled_blob))

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        mdata = json.dumps(manifest.__dict__).encode()
        info = tarfile.TarInfo(MANIFEST_NAME)
        info.size = len(mdata)
        tar.addfile(info, io.BytesIO(mdata))
        for name, data in entries:
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))

    with gzip.open(out_path, "wb") as f:
        f.write(buf.getvalue())
    return manifest


class BundleError(ValueError):
    pass


def _policy_to_dict(pol: model.Policy) -> dict:
    raise BundleError(
        f"policy {pol.fqn()} has no raw document (store does not retain source "
        "text); bundle from a disk or sqlite store"
    )


class BundleStore(Store):
    """Read-only store backed by a bundle file (the BinaryStore analogue)."""

    driver = "bundle"

    def __init__(
        self,
        path: str,
        verify_checksum: bool = True,
        signing_key: Optional[bytes] = None,
    ):
        super().__init__()
        self.path = path
        self.signing_key = signing_key
        self._policies: dict[str, model.Policy] = {}
        self._schemas: dict[str, bytes] = {}
        self._compiled: Optional[list] = None
        self.manifest: Optional[BundleManifest] = None
        self._load(verify_checksum)

    def _load(self, verify_checksum: bool) -> None:
        from .util import gctune

        with gctune.build_phase():
            self._load_inner(verify_checksum)

    def _load_inner(self, verify_checksum: bool) -> None:
        with gzip.open(self.path, "rb") as f:
            data = f.read()
        entries: list[tuple[str, bytes]] = []
        compiled_blob: Optional[bytes] = None
        with tarfile.open(fileobj=io.BytesIO(data)) as tar:
            for member in tar.getmembers():
                fh = tar.extractfile(member)
                if fh is None:
                    continue
                content = fh.read()
                if member.name == MANIFEST_NAME:
                    self.manifest = BundleManifest(**json.loads(content))
                elif member.name == COMPILED_NAME:
                    compiled_blob = content
                else:
                    entries.append((member.name, content))
        if self.manifest is None:
            raise ValueError(f"bundle {self.path} has no manifest")
        if self.manifest.version > BUNDLE_VERSION:
            raise ValueError(
                f"bundle {self.path} was created by a newer compiler (v{self.manifest.version})"
            )
        if verify_checksum:
            digest = hashlib.sha256()
            for name, content in sorted(entries):
                digest.update(name.encode())
                digest.update(hashlib.sha256(content).digest())
            if digest.hexdigest() != self.manifest.checksum:
                raise ValueError(f"bundle {self.path} checksum mismatch (corrupted artifact)")
        for name, content in entries:
            if name.startswith("policies/"):
                for pol in parse_policies(content.decode("utf-8"), source=name):
                    self._policies[pol.fqn()] = pol
            elif name.startswith("_schemas/"):
                self._schemas[name[len("_schemas/"):]] = content
        # compiled IR: structured decode (no code execution — safe for
        # untrusted bundles). Gates: integrity checksum, compiler version
        # (migration analogue of ruletable.go:935-970), and — when a signing
        # key is configured — HMAC authenticity. On any mismatch the bundled
        # sources above simply recompile.
        authentic = True
        if self.signing_key and compiled_blob is not None:
            want = hmac.new(self.signing_key, compiled_blob, hashlib.sha256).hexdigest()
            authentic = hmac.compare_digest(want, self.manifest.compiled_signature or "")
        if (
            authentic
            and compiled_blob is not None
            and self.manifest.compiler_version == COMPILER_VERSION
            and hashlib.sha256(compiled_blob).hexdigest() == self.manifest.compiled_checksum
        ):
            try:
                self._compiled = decode_compiled(compiled_blob)
            except CodecError:  # shape drift: fall back to sources
                self._compiled = None

    def get_compiled(self) -> Optional[list]:
        """The bundled compiled policy IR, if present and valid — lets the
        loader skip parse+compile entirely (the RuleTableStore analogue)."""
        return self._compiled

    def get_all(self) -> list[model.Policy]:
        return [p for p in self._policies.values() if not p.disabled]

    def get(self, fqn: str) -> Optional[model.Policy]:
        return self._policies.get(fqn)

    def get_schema(self, schema_id: str) -> Optional[bytes]:
        return self._schemas.get(schema_id)

    def list_schema_ids(self) -> list[str]:
        return sorted(self._schemas)


register_driver("bundle", lambda conf: BundleStore(
    path=conf.get("path", "bundle.crbp"),
    verify_checksum=bool(conf.get("verifyChecksum", True)),
    signing_key=conf["signingKey"].encode() if conf.get("signingKey") else None,
))
