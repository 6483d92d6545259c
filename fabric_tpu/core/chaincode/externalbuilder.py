"""External-builder contract: detect / build / release / run.

Rebuild of `core/container/externalbuilder/externalbuilder.go`: the
supported way to run chaincode this peer did not link in-process and
does not hand-manage as CCaaS. Operators configure builders in
core.yaml —

    chaincode:
      externalBuilders:
        - name: my-builder
          path: /opt/builders/my-builder     # has bin/{detect,build,release,run}
          propagateEnvironment: [GOCACHE, HOME]

and each builder is a directory of four executables invoked exactly
like the reference's:

    bin/detect  SOURCE_DIR METADATA_DIR            rc 0 = claim
    bin/build   SOURCE_DIR METADATA_DIR BUILD_DIR
    bin/release BUILD_DIR  RELEASE_DIR             (optional)
    bin/run     BUILD_DIR  ARTIFACTS_DIR           (long-running)

Chaincode packages are .tar.gz archives holding `metadata.json`
({"type": ..., "label": ...}) and the source tree — the reference's
package shape (`core/chaincode/persistence/chaincode_package.go`)
without the nested code.tar.gz indirection.

Connection model (documented divergence): this framework's chaincode
transport is peer→chaincode in both modes (see external.py). A builder
whose release step writes `chaincode/server/connection.json`
({"address": host:port}) declares a server-mode (CCaaS) chaincode the
peer dials directly; otherwise `bin/run` is spawned with
ARTIFACTS_DIR/chaincode.json telling it which address to LISTEN on,
and the peer dials that. The reference's reverse (chaincode-dials-
peer) registration flow does not exist here.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import socket
import subprocess
import tarfile
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

logger = logging.getLogger("chaincode.externalbuilder")


class BuildError(Exception):
    pass


@dataclass
class BuilderConfig:
    name: str
    path: str
    propagate_environment: tuple = ()

    @classmethod
    def from_config(cls, cfg: dict) -> "BuilderConfig":
        return cls(name=cfg.get("Name") or cfg.get("name", ""),
                   path=cfg.get("Path") or cfg.get("path", ""),
                   propagate_environment=tuple(
                       cfg.get("PropagateEnvironment")
                       or cfg.get("propagateEnvironment") or ()))


@dataclass
class LaunchedChaincode:
    name: str
    package_id: str
    address: str
    client: object
    process: Optional[subprocess.Popen] = None
    build_dir: str = ""

    def stop(self) -> None:
        try:
            self.client.close()
        # ftpu-lint: allow-swallow(teardown close of a possibly-dead
        # chaincode client; the process terminate/kill below is the
        # real stop)
        except Exception:
            pass
        if self.process is not None and self.process.poll() is None:
            self.process.terminate()
            try:
                self.process.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.process.kill()


def package_id_of(package_path: str, label: str = "") -> str:
    """label:sha256 — the reference's package identifier shape."""
    h = hashlib.sha256()
    with open(package_path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return f"{label or 'cc'}:{h.hexdigest()}"


def write_package(path: str, metadata: dict, sources: dict) -> str:
    """Create a chaincode package: metadata.json + src/<files>."""
    import io
    with tarfile.open(path, "w:gz") as tar:
        def add(name, data: bytes):
            info = tarfile.TarInfo(name)
            info.size = len(data)
            info.mode = 0o644
            tar.addfile(info, io.BytesIO(data))
        add("metadata.json", json.dumps(metadata).encode())
        for rel, data in sources.items():
            add(f"src/{rel}", data)
    return path


class ExternalBuilderRegistry:
    """Orders builders and drives the 4-phase contract per package."""

    def __init__(self, builders: Sequence[BuilderConfig],
                 build_root: str):
        self._builders = list(builders)
        self._root = build_root
        os.makedirs(build_root, exist_ok=True)

    # -- phases --

    def _env(self, b: BuilderConfig) -> dict:
        env = {"PATH": os.environ.get("PATH", "")}
        for k in b.propagate_environment:
            if k in os.environ:
                env[k] = os.environ[k]
        if b.name == "ftpu-python":
            # the built-in platform's run script hosts the chaincode
            # with the framework's own shim/server modules: make THIS
            # process's fabric_tpu importable in the child regardless
            # of how the peer itself was launched
            import fabric_tpu
            pkg_root = os.path.dirname(os.path.dirname(
                os.path.abspath(fabric_tpu.__file__)))
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in (env.get("PYTHONPATH", ""), pkg_root) if p)
        return env

    def _exec(self, b: BuilderConfig, phase: str, args: list,
              check: bool = True) -> int:
        exe = os.path.join(b.path, "bin", phase)
        if not os.path.exists(exe):
            if phase == "release":
                return 0           # optional phase (reference semantics)
            raise BuildError(f"builder {b.name}: missing bin/{phase}")
        proc = subprocess.run([exe, *args], env=self._env(b),
                              capture_output=True, text=True)
        if proc.returncode != 0 and check:
            raise BuildError(
                f"builder {b.name} {phase} failed (rc {proc.returncode}): "
                f"{proc.stderr.strip() or proc.stdout.strip()}")
        return proc.returncode

    def detect(self, source_dir: str, metadata_dir: str
               ) -> Optional[BuilderConfig]:
        """First builder whose bin/detect exits 0 claims the package."""
        for b in self._builders:
            exe = os.path.join(b.path, "bin", "detect")
            if not os.path.exists(exe):
                continue
            rc = subprocess.run([exe, source_dir, metadata_dir],
                                env=self._env(b),
                                capture_output=True).returncode
            if rc == 0:
                return b
        return None

    # -- the full pipeline --

    def launch(self, name: str, package_path: str, support,
               connect_timeout_s: float = 15.0) -> LaunchedChaincode:
        """Unpack → detect → build → release → run/connect → register.

        `support` is the peer's ChaincodeSupport; on success the
        chaincode is registered under `name` and endorsement flows to
        it transparently (reference: externalbuilder.Run + the
        chaincode_support launch path).
        """
        from fabric_tpu.core.chaincode.external import (
            ExternalChaincodeClient,
        )

        pkg_id = package_id_of(package_path)
        work = os.path.join(
            self._root, pkg_id.split(":", 1)[1][:16])
        src = os.path.join(work, "src")
        meta = os.path.join(work, "metadata")
        bld = os.path.join(work, "bld")
        rel = os.path.join(work, "release")
        run_meta = os.path.join(work, "artifacts")
        for d in (src, meta, bld, rel, run_meta):
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d)

        with tarfile.open(package_path, "r:gz") as tar:
            for member in tar.getmembers():
                target = os.path.normpath(member.name)
                if target.startswith(("/", "..")):
                    raise BuildError(f"unsafe path in package: "
                                     f"{member.name!r}")
                if target == "metadata.json":
                    tar.extract(member, meta, filter="data")
                elif target.startswith("src/"):
                    member.name = target[4:]
                    tar.extract(member, src, filter="data")

        builder = self.detect(src, meta)
        if builder is None:
            raise BuildError(
                f"no configured external builder claims package "
                f"{pkg_id} (builders: "
                f"{[b.name for b in self._builders]})")
        logger.info("builder %s claimed %s", builder.name, pkg_id)
        self._exec(builder, "build", [src, meta, bld])
        self._exec(builder, "release", [bld, rel])

        conn_path = os.path.join(rel, "chaincode", "server",
                                 "connection.json")
        process = None
        if os.path.exists(conn_path):
            with open(conn_path) as f:
                address = json.load(f)["address"]
            logger.info("%s: server-mode chaincode at %s", name, address)
        else:
            # spawn via bin/run; tell it where to LISTEN
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                address = "127.0.0.1:%d" % s.getsockname()[1]
            with open(os.path.join(run_meta, "chaincode.json"),
                      "w") as f:
                json.dump({"address": address, "chaincode_id": pkg_id,
                           "name": name}, f)
            exe = os.path.join(builder.path, "bin", "run")
            if not os.path.exists(exe):
                raise BuildError(
                    f"builder {builder.name}: no connection.json "
                    "released and no bin/run to start the chaincode")
            process = subprocess.Popen(
                [exe, bld, run_meta], env=self._env(builder),
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

        client = ExternalChaincodeClient(
            name, address,
            metrics_provider=getattr(support, "metrics_provider",
                                     None))
        deadline = time.monotonic() + connect_timeout_s
        last = None
        while True:
            try:
                client.ping()
                break
            except Exception as e:           # noqa: BLE001
                last = e
                if process is not None and process.poll() is not None:
                    raise BuildError(
                        f"chaincode process exited rc "
                        f"{process.returncode} before serving") from e
                if time.monotonic() > deadline:
                    if process is not None:
                        process.terminate()
                    raise BuildError(
                        f"chaincode at {address} not reachable: "
                        f"{last}") from e
                time.sleep(0.1)
        support.register(name, client)
        return LaunchedChaincode(name=name, package_id=pkg_id,
                                 address=address, client=client,
                                 process=process, build_dir=bld)


def builtin_python_builder() -> BuilderConfig:
    """The framework's built-in python platform (the role the docker
    controller + core/chaincode/platforms play in the reference:
    arbitrary source tree → running chaincode process with ZERO
    operator-provided builders — here daemon-free, as a subprocess
    hosting ChaincodeServer). Last in detection order, so operator
    builders always win."""
    here = os.path.dirname(os.path.abspath(__file__))
    return BuilderConfig(
        name="ftpu-python",
        path=os.path.join(here, "builtin_builder"),
        # the run script imports fabric_tpu + jax-free shim modules
        propagate_environment=["PYTHONPATH", "HOME", "LANG",
                               "JAX_PLATFORMS"])


def registry_from_config(cfg: dict, build_root: str
                         ) -> ExternalBuilderRegistry:
    """core.yaml `chaincode.externalBuilders` → registry, plus the
    built-in python platform (disable with
    `chaincode.disableBuiltinPlatform: true`)."""
    builders = [BuilderConfig.from_config(b)
                for b in (cfg or {}).get("externalBuilders", [])]
    if not (cfg or {}).get("disableBuiltinPlatform"):
        builders.append(builtin_python_builder())
    return ExternalBuilderRegistry(builders, build_root)
