"""Environment fingerprinting for the continuous-benchmarking devhub.

Every benchmark artifact (bench.py's JSON line / devhub.jsonl row, the
cli `benchmark` BENCH_JSON line, BENCH_r*.json round files) is stamped
with a machine-readable profile of the environment that produced it, so
a number recorded on a TPU host is distinguishable from the 2-core dev
container *by construction* (ROADMAP "accelerator truth round";
reference devhub.zig uploads per-merge metrics keyed by runner).

The stable identity is `profile_id`: a short hash over the fields that
determine what a benchmark number *means* —

    system / machine / cpu_count   (the host)
    accel_backend / accel_kind / accel_count
                                   (the accelerator jax would actually
                                    use; "none" when jax's default
                                    backend is plain XLA-CPU, so a
                                    JAX_PLATFORMS=cpu run on a TPU host
                                    correctly fingerprints as cpu-only)

Library versions and git revision are recorded alongside but NOT hashed:
a jax upgrade on the same host continues the same trajectory (the
change-point detector in tools/devhub.py will surface it if it moves the
numbers; that is a detectable step, not a different machine).

This module must stay importable without jax (bench.py's parent process
is deliberately jax-free until the forked sections finish — see
bench.py's section ordering); jax is only imported inside
`fingerprint(allow_jax=True)`, and callers in jax-free processes pass
`allow_jax=False` (or gate on `"jax" in sys.modules`).

Profile-matching rules (docs/DEVHUB.md): tools/bench_gate.py compares
candidate vs baseline `profile_id` and refuses a numeric verdict on
mismatch; artifacts recorded before fingerprinting existed (the
pre-round-17 devhub.jsonl rows) are adopted as `LEGACY_PROFILE` — the
dev container every one of them ran on — so the existing trajectory
stays comparable.
"""

from __future__ import annotations

import hashlib
import json
import os
import platform

# The fields that participate in the profile_id hash, in hash order.
# Extending this tuple changes every profile_id — treat it like a wire
# format (add new facts as recorded-not-hashed keys instead).
PROFILE_ID_FIELDS = (
    "system",
    "machine",
    "cpu_count",
    "accel_backend",
    "accel_kind",
    "accel_count",
)

# The environment every un-fingerprinted artifact in this repo was
# recorded on: the Linux/x86_64 2-core, no-accelerator dev container
# (ROADMAP: "every number in BENCH_r*.json is a 2-core no-accelerator
# container"). bench_gate/devhub adopt this profile for legacy
# baselines/rows so the early trajectory stays comparable; if the
# container shape ever changes, legacy artifacts correctly stop
# matching.
LEGACY_PROFILE = {
    "system": "Linux",
    "machine": "x86_64",
    "cpu_count": 2,
    "accel_backend": "none",
    "accel_kind": "none",
    "accel_count": 0,
}


def profile_id_from(fields: dict) -> str:
    """Stable 12-hex-char id over PROFILE_ID_FIELDS (missing keys hash
    as null, so a partial dict still gets a deterministic id)."""
    blob = json.dumps([fields.get(k) for k in PROFILE_ID_FIELDS])
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:12]


def legacy_profile_id() -> str:
    """profile_id adopted for artifacts recorded before fingerprinting
    existed (the dev-container profile, see LEGACY_PROFILE)."""
    return profile_id_from(LEGACY_PROFILE)


def fingerprint(allow_jax: bool = True) -> dict:
    """The full environment profile of THIS process, profile_id included.

    allow_jax=False keeps the probe jax-free (the accelerator fields
    report "none"); use it from processes that must not pull in the jax
    runtime — a parent whose child holds the chip — and add the fields
    the child reported with with_accelerator()."""
    info = {
        "system": platform.system(),
        "machine": platform.machine(),
        "cpu_count": int(os.cpu_count() or 0),
        "accel_backend": "none",
        "accel_kind": "none",
        "accel_count": 0,
        "python": platform.python_version(),
    }
    try:  # numpy is a hard dependency everywhere this runs, but stay safe
        import numpy

        info["numpy"] = numpy.__version__
    except Exception:  # pragma: no cover - numpy is baked into the image
        pass
    if allow_jax:
        # A probe that was asked for and fails RAISES: a chip host whose
        # runtime would not initialise here (another process holds the
        # chip) must never be stamped as a cpu-only profile.
        import jax

        info["jax"] = jax.__version__
        devices = jax.devices()
        return with_accelerator(
            info, devices[0].platform, devices[0].device_kind, len(devices)
        )
    info["profile_id"] = profile_id_from(info)
    return info


def with_accelerator(info: dict, platform: str, kind: str, count: int) -> dict:
    """`info` with the accelerator fields taken from what the process
    that holds the device reported (this one, or a server's `listening`
    line), profile_id recomputed. XLA-CPU and "no backend" both stamp as
    "none": a JAX_PLATFORMS=cpu run on a TPU host is a cpu-only profile."""
    info = dict(info)
    if platform not in ("cpu", "none"):
        info["accel_backend"] = str(platform)
        info["accel_kind"] = str(kind)
        info["accel_count"] = int(count)
    info["profile_id"] = profile_id_from(info)
    return info


def record_profile_id(record: dict) -> str:
    """The profile_id a devhub/bench record belongs to: its own stamp
    when fingerprinted, the legacy dev-container profile otherwise."""
    env = record.get("env")
    if not isinstance(env, dict):
        env = (record.get("extra") or {}).get("env") if isinstance(
            record.get("extra"), dict
        ) else None
    if isinstance(env, dict) and env.get("profile_id"):
        return str(env["profile_id"])
    pid = record.get("profile_id")
    if pid:
        return str(pid)
    return legacy_profile_id()
