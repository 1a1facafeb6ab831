"""msgpack pytree checkpointing (orbax is not available offline).

Layout: <dir>/step_<n>.msgpack, each file a self-describing tree where
arrays are {"__nd__": shape, "dtype": str, "data": bytes}. Atomic writes
(tmp + rename) so a killed run never leaves a torn checkpoint.
"""
from __future__ import annotations

import os
import re
import tempfile
from typing import Any, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np


class ZooMismatchError(ValueError):
    """A checkpoint's cohort families don't match the live federation's
    zoo. Raised BEFORE any state is assigned (a partial restore would
    leave the federation half-overwritten), naming exactly which families
    are missing on each side — not a shape error deep in pytree
    unflattening. Subclasses ValueError so legacy ``except ValueError``
    callers keep working."""


def _encode(obj: Any):
    if isinstance(obj, (jnp.ndarray, np.ndarray)):
        arr = np.asarray(obj)
        return {"__nd__": list(arr.shape), "dtype": str(arr.dtype),
                "data": arr.tobytes()}
    if isinstance(obj, dict):
        return {"__map__": {k: _encode(v) for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"__seq__": [_encode(v) for v in obj],
                "tuple": isinstance(obj, tuple)}
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return {"__leaf__": obj}
    raise TypeError(f"cannot checkpoint {type(obj)}")


def _decode(obj: Any):
    if "__nd__" in obj:
        arr = np.frombuffer(obj["data"], dtype=np.dtype(obj["dtype"]))
        return jnp.asarray(arr.reshape(obj["__nd__"]))
    if "__map__" in obj:
        return {k: _decode(v) for k, v in obj["__map__"].items()}
    if "__seq__" in obj:
        seq = [_decode(v) for v in obj["__seq__"]]
        return tuple(seq) if obj.get("tuple") else seq
    return obj["__leaf__"]


def save_pytree(path: str, tree: Any) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    payload = msgpack.packb(_encode(jax.tree.map(lambda x: x, tree)),
                            use_bin_type=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)))
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def restore_pytree(path: str) -> Any:
    with open(path, "rb") as f:
        return _decode(msgpack.unpackb(f.read(), raw=False))


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(m.group(1)) for f in os.listdir(ckpt_dir)
             if (m := re.match(r"step_(\d+)\.msgpack$", f))]
    return max(steps) if steps else None


def save_federation(ckpt_dir: str, fed, step: int, bus=None) -> None:
    """Persist the full federation: every cohort's stacked params/opt state
    + the server state (repository, graph, quality) + the messenger wire
    codec names the run was using (so a resumed run speaks the same
    format) + the RNG key and current distill targets. Device-sharded
    cohorts persist their REAL rows only — checkpoint files are
    device-layout-agnostic and restore onto any mesh (or none).

    ``bus`` (a ``ServerBus``) additionally persists the runtime's trigger
    and staleness bookkeeping (uploads-since-fire counters, per-client
    last-upload times, wire-byte meters): without it a restored every-k or
    quorum engine double-fires or skips its first server round."""
    tree = {
        "server": fed.server._asdict(),
        "zoo": [c.family_name for c in fed.cohorts],
        "cohorts": [{
            "family": c.family_name,
            "client_ids": np.asarray(c.client_ids),
            "params": c.real_params,
            "opt_state": _optstate_to_tree(c.real_opt_state),
        } for c in fed.cohorts],
        "wire": {"uplink": getattr(fed, "uplink", "dense32"),
                 "downlink": getattr(fed, "downlink", "dense32")},
        "round": step,
    }
    if fed.rng is not None:
        tree["rng"] = np.asarray(jax.random.key_data(fed.rng))
    if fed.targets is not None:
        tree["targets"] = fed.targets
    if bus is not None:
        tree["bus"] = bus.state_dict()
    save_pytree(os.path.join(ckpt_dir, f"step_{step}.msgpack"), tree)


def restore_federation(ckpt_dir: str, fed, step: Optional[int] = None,
                       bus=None):
    """Restore in place; cohort order/families must match. Legacy files
    (written before the wire subsystem) restore as ``dense32`` — the
    bit-identical pass-through codec they implicitly used. Files without a
    ``bus`` section restore the given bus with ZEROED counters (the legacy
    contract); files without rng/targets leave those untouched. Cohorts
    that run device-sharded re-apply their ghost padding + placement after
    the real rows load."""
    from repro.core.server import ServerState
    from repro.core.wire import as_codec
    step = step if step is not None else latest_step(ckpt_dir)
    tree = restore_pytree(os.path.join(ckpt_dir, f"step_{step}.msgpack"))
    server = dict(tree["server"])
    # the collaboration graph's (N,N) selection matrix was state before
    # graphs went K-sparse; each fire rebuilds the graph, so drop it
    server.pop("weights", None)
    if "div_cache" not in server:
        # pre-delta-path checkpoint: rebuild the divergence cache from the
        # restored repository so incremental graph updates stay exact
        # (ops dispatch: chunked at large N, platform backend)
        from repro.kernels import ops
        server["div_cache"] = ops.pairwise_kl(server["repo_logp"])
    # validate the zoo BEFORE assigning anything: a family mismatch must
    # be a clean typed error naming the families, never a half-restored
    # federation or a pytree-unflatten crash
    saved_fams = [s["family"] for s in tree["cohorts"]]
    live_fams = [c.family_name for c in fed.cohorts]
    if saved_fams != live_fams:
        missing = [f for f in saved_fams if f not in live_fams]
        extra = [f for f in live_fams if f not in saved_fams]
        detail = []
        if missing:
            detail.append(f"checkpoint families missing from the live "
                          f"zoo: {missing}")
        if extra:
            detail.append(f"live families absent from the checkpoint: "
                          f"{extra}")
        if not detail:
            detail.append("cohort order changed")
        raise ZooMismatchError(
            f"cohort layout changed: checkpoint has {saved_fams}, live "
            f"federation has {live_fams} — {'; '.join(detail)}")
    fed.server = ServerState(**server)
    codecs = tree.get("wire") or {}
    fed.uplink = codecs.get("uplink", "dense32")
    fed.downlink = codecs.get("downlink", "dense32")
    as_codec(fed.uplink), as_codec(fed.downlink)   # names must resolve
    if "rng" in tree:
        fed.rng = jax.random.wrap_key_data(jnp.asarray(tree["rng"]))
    if "targets" in tree:
        fed.targets = tree["targets"]
    for c, saved in zip(fed.cohorts, tree["cohorts"]):
        c.params = saved["params"]
        c.opt_state = _optstate_from_tree(saved["opt_state"],
                                          c.real_opt_state)
        if c.sharding is not None:
            from repro.sharding import repad_cohort_arrays
            repad_cohort_arrays(c)
    if bus is not None:
        bus.load_state_dict(tree.get("bus"))
    return step


def _optstate_to_tree(s):
    if hasattr(s, "_asdict"):
        return {"__nt__": type(s).__name__, **s._asdict()}
    return s


def _optstate_from_tree(tree, template):
    if isinstance(tree, dict) and "__nt__" in tree:
        vals = {k: v for k, v in tree.items() if k != "__nt__"}
        return type(template)(**vals)
    return tree
