"""Runtime environments: per-task/actor execution environments.

Reference: python/ray/_private/runtime_env/ — a plugin system (env_vars,
working_dir, py_modules, pip, conda, container, mpi, nsight) applied by a
per-node agent; the raylet keys idle workers by runtime-env hash so
environments never cross-contaminate (src/ray/raylet/worker_pool.h:174).

Rebuild: the same two pieces, trimmed to what a TPU pod needs —

- a **plugin registry** (:func:`register_plugin`): each key in the env dict
  maps to a setup function applied inside the worker process before the
  first task of that env runs. Built-ins: ``env_vars``, ``working_dir``,
  ``py_modules``, ``config``, and ``pip`` (per-hash ``pip install
  --target`` — offline-capable with local wheels/dirs or gs:// wheels;
  see :func:`_setup_pip`). ``image_uri`` launches the WORKER ITSELF
  inside a container image (spawn-time, not in-process — see
  ray_tpu/runtime_env/container.py; env hashes prefix ``img:`` so the
  scheduler never lets a pristine host worker adopt one). ``conda``
  raises :class:`RuntimeEnvSetupError` — workers share the host
  interpreter; use ``image_uri`` (or bake deps into the pod image).
- **worker affinity by env hash**: the controller only dispatches an
  env-tagged task to a worker already in that env or to a pristine worker
  (which then becomes env-tagged) — reference behavior, collapsed into the
  central scheduler.

Env application is sticky per worker (the reference dedicates workers the
same way); a worker never switches between two non-empty envs.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
from typing import Any, Callable, Dict, Optional

from ray_tpu.exceptions import RuntimeEnvSetupError

_INTERNAL_KEYS = {"__actor_name__", "__trace_ctx__"}

_plugins: Dict[str, Callable[[Any], None]] = {}


def register_plugin(key: str, setup: Callable[[Any], None]):
    """Register a runtime-env key handler (reference: RuntimeEnvPlugin)."""
    _plugins[key] = setup


class RuntimeEnv(dict):
    """Validated runtime-env mapping (reference: ray.runtime_env.RuntimeEnv)."""

    def __init__(
        self,
        *,
        env_vars: Optional[Dict[str, str]] = None,
        working_dir: Optional[str] = None,
        py_modules: Optional[list] = None,
        config: Optional[dict] = None,
        image_uri: Optional[str] = None,
        **extra,
    ):
        super().__init__()
        if image_uri is not None:
            if not isinstance(image_uri, str) or not image_uri:
                raise ValueError("image_uri must be a non-empty string")
            self["image_uri"] = image_uri
        if env_vars is not None:
            if not all(isinstance(k, str) and isinstance(v, str) for k, v in env_vars.items()):
                raise ValueError("env_vars must be a str→str mapping")
            self["env_vars"] = dict(env_vars)
        if working_dir is not None:
            self["working_dir"] = working_dir
        if py_modules is not None:
            self["py_modules"] = list(py_modules)
        if config is not None:
            self["config"] = dict(config)
        for k, v in extra.items():
            if k not in _plugins and k not in ("pip", "conda"):
                raise ValueError(f"unknown runtime_env key: {k!r}")
            self[k] = v


def strip_internal(env: Optional[dict]) -> dict:
    return {k: v for k, v in (env or {}).items() if k not in _INTERNAL_KEYS}


def env_hash(env: Optional[dict]) -> str:
    """Stable hash keying worker reuse (reference: worker_pool runtime-env
    hash in the lease request). Container envs hash with an ``img:``
    prefix — the scheduler uses it to require spawn-time (exact-match)
    workers instead of letting a pristine host worker adopt the env."""
    e = strip_internal(env)
    if not e:
        return ""
    blob = json.dumps(e, sort_keys=True, default=str).encode()
    digest = hashlib.blake2b(blob, digest_size=8).hexdigest()
    return f"img:{digest}" if e.get("image_uri") else digest


# ---------------------------------------------------------------------------
# Built-in plugins (applied inside the worker process)
# ---------------------------------------------------------------------------
def _setup_env_vars(value: Dict[str, str]):
    tpu_keys = sorted(k for k in value if k.startswith("TPU_"))
    if tpu_keys:
        from ray_tpu.accelerators.tpu import jax_backend_initialized

        if jax_backend_initialized() and sys.modules["jax"].default_backend() == "tpu":
            # libtpu read its chip set when the backend came up; setting
            # it now would silently leave the actor on the wrong chips.
            raise RuntimeEnvSetupError(
                f"cannot apply {tpu_keys}: "
                "this pooled worker already opened a jax backend (an earlier "
                "task without TPU resources touched jax). Only tasks/actors "
                "that hold TPU resources may initialize jax on a TPU host."
            )
    os.environ.update(value)


def _setup_working_dir(value: str):
    # Local-path working dirs only: in the single-image TPU-pod deployment
    # all hosts share the filesystem layout, so there is no URI
    # upload/download step (reference's GCS packaging,
    # _private/runtime_env/working_dir.py, is an artifact of heterogeneous
    # clusters). Zip archives are extracted beside the session.
    path = value
    if path.endswith(".zip"):
        import tempfile
        import zipfile

        dest = tempfile.mkdtemp(prefix="rt_env_wd_")
        with zipfile.ZipFile(path) as z:
            z.extractall(dest)
        path = dest
    if not os.path.isdir(path):
        raise RuntimeEnvSetupError(f"working_dir does not exist: {value}")
    os.chdir(path)
    sys.path.insert(0, path)


def _setup_py_modules(value: list):
    for mod in value:
        if not os.path.exists(mod):
            raise RuntimeEnvSetupError(f"py_modules path does not exist: {mod}")
        parent = mod if os.path.isdir(mod) else os.path.dirname(mod)
        if parent not in sys.path:
            sys.path.insert(0, parent)


def _setup_config(value: dict):
    pass  # setup-timeout etc.; carried for API parity


def _setup_pip(value):
    """Per-env-hash pip install into a --target directory prepended to
    sys.path (reference: _private/runtime_env/pip.py builds a venv per
    env; workers here share the interpreter, so a target dir gives the
    same isolation-by-precedence at a fraction of the cost).

    Specs may be package names (needs an index — TPU fleets usually run
    hermetic, so expect local use), LOCAL paths (wheels or source dirs;
    built with --no-build-isolation against the image's setuptools —
    fully offline), or gs://-style URIs staged through cloudfs. The
    install runs once per unique spec list; concurrent workers wait on
    the winner (reference: the runtime-env agent's per-URI refcounts)."""
    import hashlib
    import json as _json
    import subprocess
    import tempfile
    import time

    if isinstance(value, dict):
        packages = list(value.get("packages", []))
        extra_args = list(value.get("pip_install_options", []))
    else:
        packages = list(value)
        extra_args = []
    if not packages:
        return
    def _spec_key(spec: str):
        # local specs key on (path, mtime, size) so a rebuilt wheel or
        # edited source dir gets a fresh env instead of the stale cache
        # (per-file content hashing is the reference's heavier answer);
        # dir mtime only tracks top-level changes — `touch` the dir after
        # deep edits, or bump the package version.
        try:
            st = os.stat(spec)
            return [spec, int(st.st_mtime_ns), st.st_size]
        except OSError:
            return [spec]

    digest = hashlib.blake2s(
        _json.dumps([sorted(map(_spec_key, packages)), sorted(extra_args)]).encode()
    ).hexdigest()[:16]
    base = os.path.join(tempfile.gettempdir(), "ray_tpu", "pip_envs")
    root = os.path.join(base, digest)
    done = os.path.join(root, ".done")
    lock = root + ".lock"
    while not os.path.exists(done):
        os.makedirs(base, exist_ok=True)
        try:
            os.close(os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
            owner = True
        except FileExistsError:
            # stale lock from a crashed owner (OOM-killed mid-install)
            # must not wedge the env forever — take it over past the
            # staleness horizon
            try:
                # live owners heartbeat the lock mtime every 5s, so 120s
                # of silence really means a dead owner
                if time.time() - os.path.getmtime(lock) > 120:
                    os.unlink(lock)
                    continue
            except FileNotFoundError:
                continue  # owner just finished/failed — re-evaluate
            owner = False
        if owner:
            import threading

            # heartbeat thread keeps the lock mtime fresh through BOTH
            # staging and the pip run — the 120s takeover check must only
            # ever fire on a genuinely dead owner
            stop_hb = threading.Event()

            def _hb():
                while not stop_hb.is_set():
                    try:
                        os.utime(lock)
                    except OSError:
                        return
                    stop_hb.wait(5)

            threading.Thread(target=_hb, daemon=True).start()
            try:
                os.makedirs(root, exist_ok=True)
                staged = []
                for i, spec in enumerate(packages):
                    from ray_tpu.utils import cloudfs

                    if cloudfs.is_uri(spec):
                        # index prefix: same-basename URIs must not collide
                        local = os.path.join(
                            root, f"{i}-{os.path.basename(spec)}"
                        )
                        cloudfs.download_file(spec, local)  # streamed
                        staged.append(local)
                    else:
                        staged.append(spec)
                cmd = [
                    sys.executable, "-m", "pip", "install", "--quiet",
                    "--no-build-isolation",  # offline: ambient setuptools
                    "--target", root, *extra_args, *staged,
                ]
                r = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
                if r.returncode != 0:
                    raise RuntimeEnvSetupError(
                        f"pip install failed: {r.stderr[-800:] or r.stdout[-800:]}"
                    )
                open(done, "w").close()
            finally:
                stop_hb.set()
                try:
                    os.unlink(lock)
                except FileNotFoundError:
                    pass
            break
        else:
            # waiter deadline keys off the owner's lock heartbeat (the
            # owner utimes the lock every 5s through BOTH gs:// staging —
            # which has no timeout of its own — and the pip run): a
            # slow-but-alive install is never failed. A stale heartbeat
            # means a dead owner: loop back to the acquisition path, whose
            # 120s takeover check unlinks the stale lock so THIS worker
            # finishes the install itself instead of failing its task.
            stale = False
            while not os.path.exists(done):
                try:
                    if time.time() - os.path.getmtime(lock) > 120:
                        stale = True
                        break
                except OSError:
                    pass  # lock vanished — the exists() checks decide
                if not os.path.exists(lock):
                    # owner exited: success wrote .done FIRST, so re-check
                    # it before declaring failure (TOCTOU)
                    if os.path.exists(done):
                        break
                    raise RuntimeEnvSetupError(
                        "concurrent pip env install failed (no .done marker)"
                    )
                time.sleep(0.25)
            if stale:
                continue
            break
    if root not in sys.path:
        sys.path.insert(0, root)


def _setup_unsupported(kind: str):
    def fail(value):
        raise RuntimeEnvSetupError(
            f"runtime_env[{kind!r}] is not supported: workers share the host "
            "interpreter and TPU fleets run hermetic images with no package "
            "egress. Bake dependencies into the image, or ship local code "
            "with py_modules/working_dir/pip (local wheels)."
        )

    return fail


def _setup_jax_profiler_hook(value):
    from ray_tpu.runtime_env.jax_profiler import _setup_jax_profiler

    _setup_jax_profiler(value)


def _setup_image_uri(value):
    # No-op INSIDE the worker: the image took effect at spawn time (the
    # node wrapped the worker command via the container runtime —
    # runtime_env/container.py); by the time a task applies its env, the
    # process is already in the image.
    pass


register_plugin("image_uri", _setup_image_uri)
register_plugin("env_vars", _setup_env_vars)
register_plugin("jax_profiler", _setup_jax_profiler_hook)
register_plugin("working_dir", _setup_working_dir)
register_plugin("py_modules", _setup_py_modules)
register_plugin("config", _setup_config)
register_plugin("pip", _setup_pip)
register_plugin("conda", _setup_unsupported("conda"))

# ---------------------------------------------------------------------------
# Worker-side application
# ---------------------------------------------------------------------------
_applied_hash: Optional[str] = None


def ensure_applied(env: Optional[dict]):
    """Apply ``env`` in this worker once; sticky thereafter.

    The controller's env-affinity dispatch guarantees we are only ever
    asked to apply one non-empty env per worker lifetime.
    """
    global _applied_hash
    h = env_hash(env)
    if not h or h == _applied_hash:
        return
    if _applied_hash is not None and _applied_hash != h:
        raise RuntimeEnvSetupError(
            "worker already holds a different runtime env (scheduler bug)"
        )
    for key, value in strip_internal(env).items():
        plugin = _plugins.get(key)
        if plugin is None:
            raise RuntimeEnvSetupError(f"no plugin for runtime_env key {key!r}")
        plugin(value)
    _applied_hash = h
