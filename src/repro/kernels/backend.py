"""Shared kernel-backend resolution for every Pallas kernel wrapper.

One place answers two questions the kernel modules used to answer
independently (and therefore inconsistently — see PR 6's lint rule
``literal-interpret-default``):

  * ``default_backend()`` — which dispatch path (``"pallas"`` /
    ``"interpret"`` / ``"jnp"``) ``ops.*`` wrappers use when the caller
    passes ``backend=None``.
  * ``default_interpret()`` / ``resolve_interpret()`` — whether a direct
    ``pallas_call`` wrapper runs compiled or under the Pallas interpreter
    when the caller passes ``interpret=None``.

Both honor the ``REPRO_KERNEL_BACKEND`` environment variable, the one
process-level setting, so a whole process (CI lane, benchmark, federate
run) is pinned to one path; nothing above ``ops`` takes a backend
argument. It is read at every call, so a change takes effect at the next
kernel call:

  * ``REPRO_KERNEL_BACKEND=pallas``    -> backend "pallas", interpret False
  * ``REPRO_KERNEL_BACKEND=interpret`` -> backend "interpret", interpret True
  * ``REPRO_KERNEL_BACKEND=jnp``       -> backend "jnp", interpret True
    (direct kernel calls still run, safely, under the interpreter)

Without the override the defaults come from the platform: "pallas" /
compiled on TPU, "jnp" / interpreter everywhere else, so a direct caller
never silently runs the Python interpreter on real hardware.

This module deliberately imports nothing from the kernel modules —
``ops.py`` imports all of them at module scope, so the helper must sit
below them to avoid an import cycle.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax

VALID_BACKENDS = ("pallas", "interpret", "jnp")
ENV_VAR = "REPRO_KERNEL_BACKEND"


def _env_backend() -> Optional[str]:
    env = os.environ.get(ENV_VAR)
    if env is None or env == "":
        return None
    if env not in VALID_BACKENDS:
        # ValueError (not assert) so the guard survives python -O
        raise ValueError(f"{ENV_VAR}={env!r} is not a valid backend; "
                         f"expected one of {VALID_BACKENDS}")
    return env


@functools.cache
def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def default_backend() -> str:
    """Dispatch path used when ``backend=None``: ``$REPRO_KERNEL_BACKEND``
    where it is set, else the platform default."""
    env = _env_backend()
    if env is not None:
        return env
    return "pallas" if _on_tpu() else "jnp"


def default_interpret() -> bool:
    """Platform default for ``interpret``: compiled on TPU, interpreter
    elsewhere — a direct caller never silently runs the Python
    interpreter on real hardware. ``$REPRO_KERNEL_BACKEND`` overrides."""
    env = _env_backend()
    if env is not None:
        return env != "pallas"
    return not _on_tpu()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one sanctioned ``interpret=None`` resolution for kernel
    wrappers (the ``literal-interpret-default`` lint rule enforces that
    kernels route through here / ``default_interpret`` rather than
    defaulting to a literal)."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)
