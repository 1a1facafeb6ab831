"""Federation launch CLI — drive the Federation engines from the shell.

Any registered policy, availability schedule, arrival process, and server
trigger is reachable by name (the registries are the single source of
truth; new plugins show up here with zero changes to this file):

  PYTHONPATH=src python -m repro.launch.federate --policy sqmd --rounds 40
  PYTHONPATH=src python -m repro.launch.federate --policy fedmd \
      --schedule dropout --dropout-p 0.3 --dataset sc_like

Event clock (virtual-time async runtime):

  PYTHONPATH=src python -m repro.launch.federate --clock event \
      --arrivals straggler-latency --latency 2.5 --trigger quorum
  PYTHONPATH=src python -m repro.launch.federate --clock event \
      --arrivals bursty --trigger every-k --trigger-k 10 --until 60

Messenger wire formats (bandwidth accounting lands in the summary):

  PYTHONPATH=src python -m repro.launch.federate --uplink int8 \
      --downlink topk:4 --rounds 40

A profiler trace of the run, with the program's repro.* spans (README,
"Tracing"), readable in TensorBoard or Perfetto:

  PYTHONPATH=src python -m repro.launch.federate --rounds 5 --profile runs/prof

Multi-device client sharding (cohort steps + server divergence rows shard
over a 1-D client mesh; fake host devices for CPU testing):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.federate --devices 8 --rounds 40
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time
from typing import Optional, Union

import jax

from repro.compile_cache import enable_compile_cache
from repro.core import (ArrivalProcess, AsyncFederationEngine,
                        BurstyArrivals, EveryKUploads, FederationConfig,
                        FederationEngine, HeterogeneousCadence, Protocol,
                        Quorum, RandomDropout, Schedule, ScheduleArrivals,
                        StagedJoin, Straggler, StragglerLatency, Trigger,
                        WallInterval, as_codec, precision_recall,
                        registered_arrivals, registered_codecs,
                        registered_policies, registered_triggers)
from repro.data import fmnist_like, make_splits, pad_like, sc_like
from repro.models.zoo import build_zoo, registered_families

DATASETS = {"sc_like": sc_like, "pad_like": pad_like,
            "fmnist_like": fmnist_like}
SCHEDULES = ("always-on", "staged-join", "dropout", "straggler")


def make_schedule(args, n_clients: int, rounds: int) -> Optional[Schedule]:
    if args.schedule == "staged-join":
        per = max(1, rounds // args.stages)
        join = [(i % args.stages) * per for i in range(n_clients)]
        return StagedJoin(join)
    if args.schedule == "dropout":
        return RandomDropout(p=args.dropout_p, seed=args.seed)
    if args.schedule == "straggler":
        return Straggler(fraction=args.straggler_fraction,
                         period=args.straggler_period, seed=args.seed)
    return None  # always-on


def make_arrivals(args, n_clients: int, rounds: int) -> ArrivalProcess:
    if args.arrivals == "schedule":
        return ScheduleArrivals(make_schedule(args, n_clients, rounds))
    if args.arrivals == "straggler-latency":
        return StragglerLatency(fraction=args.straggler_fraction,
                                delay=args.latency, seed=args.seed)
    if args.arrivals == "cadence":
        return HeterogeneousCadence(fast=args.cadence_fast,
                                    slow=args.cadence_slow, seed=args.seed)
    if args.arrivals == "bursty":
        return BurstyArrivals(burst_every=args.burst_every,
                              jitter=args.latency, seed=args.seed)
    # any other registered plugin: construct with its defaults
    from repro.core import get_arrivals
    return get_arrivals(args.arrivals)()


def make_trigger(args) -> Union[str, Trigger]:
    if args.trigger == "every-k":
        return EveryKUploads(k=args.trigger_k)
    if args.trigger == "interval":
        return WallInterval(period=args.trigger_period)
    if args.trigger == "quorum":
        return Quorum(frac=args.quorum_frac)
    return args.trigger  # every-upload (or any future registered name)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--policy", choices=registered_policies(),
                    default="sqmd")
    ap.add_argument("--dataset", choices=tuple(DATASETS), default="pad_like")
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--backend", choices=("pallas", "interpret", "jnp"))
    ap.add_argument("--devices", type=int,
                    help="shard the client axis over this many devices "
                         "(cohort steps + server divergence rows); on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first. Default: single-device path")
    ap.add_argument("--delta", action="store_true",
                    help="incremental O(u·N) server graph updates from the "
                         "divergence cache (vs full O(N^2) rebuild)")
    ap.add_argument("--selection", choices=("exact", "ivf"),
                    default="exact",
                    help="neighbor selection: exact dense (N,N) divergence "
                         "or the approximate IVF top-K index "
                         "(sub-quadratic; requires --delta)")
    ap.add_argument("--uplink", default="dense32",
                    help="messenger wire codec, client->server "
                         f"({', '.join(registered_codecs())}; "
                         f"'topk:K' parameterizes)")
    ap.add_argument("--downlink", default="dense32",
                    help="K^n target wire codec, server->client "
                         "(same names as --uplink)")
    ap.add_argument("--rho", type=float, default=0.8)
    ap.add_argument("--q", type=int, default=16)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--interval", type=int, default=1)
    ap.add_argument("--schedule", choices=SCHEDULES, default="always-on")
    ap.add_argument("--stages", type=int, default=3,
                    help="staged-join: number of equal join waves")
    ap.add_argument("--dropout-p", type=float, default=0.2)
    ap.add_argument("--straggler-fraction", type=float, default=0.3)
    ap.add_argument("--straggler-period", type=int, default=3)
    # --- event clock (async virtual-time runtime) ---
    ap.add_argument("--clock", choices=("sync", "event"), default="sync",
                    help="sync: round loop; event: virtual-clock runtime")
    ap.add_argument("--until", type=float,
                    help="event clock: virtual-time horizon "
                         "(default rounds-1)")
    ap.add_argument("--arrivals", choices=registered_arrivals(),
                    default="schedule",
                    help="event clock: client arrival/latency process "
                         "('schedule' shims --schedule)")
    ap.add_argument("--latency", type=float, default=2.0,
                    help="straggler-latency upload delay / bursty jitter")
    ap.add_argument("--cadence-fast", type=float, default=1.0)
    ap.add_argument("--cadence-slow", type=float, default=3.0)
    ap.add_argument("--burst-every", type=float, default=4.0)
    ap.add_argument("--trigger", choices=registered_triggers(),
                    default="every-upload",
                    help="event clock: when the server fires policy rounds")
    ap.add_argument("--trigger-k", type=int, default=8)
    ap.add_argument("--trigger-period", type=float, default=1.0)
    ap.add_argument("--quorum-frac", type=float, default=0.5)
    ap.add_argument("--zoo", default="mlp-s,mlp-m,mlp-l",
                    help="comma-separated model families "
                         f"({', '.join(registered_families())}); the "
                         "default MLP tiers are bit-identical to every "
                         "pinned trajectory")
    ap.add_argument("--assignment",
                    help="family per client: 'fam:w,...' weighted shares "
                         "(the paper's Table-I ratios) or 'fam,fam,...' "
                         "round-robin; default round-robins --zoo")
    ap.add_argument("--samples-per-client", type=int, default=60)
    ap.add_argument("--ref-size", type=int, default=120)
    ap.add_argument("--label-noise", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt")
    ap.add_argument("--profile", metavar="DIR",
                    help="record a JAX profiler trace of the run into DIR "
                         "(the repro.* spans: round, cohort step, upload, "
                         "deliver, fire, host syncs; see README, Tracing)")
    args = ap.parse_args()
    if args.rounds < 1:
        ap.error("--rounds must be >= 1")
    if args.selection == "ivf" and not args.delta:
        ap.error("--selection ivf requires --delta (the approximate index "
                 "only exists on the incremental graph path)")
    for which in ("uplink", "downlink"):
        try:
            as_codec(getattr(args, which))
        except (KeyError, ValueError) as e:
            ap.error(f"--{which}: {e}")
    enable_compile_cache()

    ds = DATASETS[args.dataset](samples_per_client=args.samples_per_client,
                                ref_size=args.ref_size)
    splits = make_splits(ds, seed=args.seed, label_noise=args.label_noise)
    try:
        from repro.models.zoo import parse_assignment
        zoo = build_zoo(args.zoo, ds.feature_len, ds.n_classes)
        # derived from len(zoo), never a hard-coded modulus: any family
        # count round-robins correctly (and weighted specs validate)
        assignment = parse_assignment(args.assignment, list(zoo),
                                      ds.n_clients)
    except (KeyError, ValueError) as e:
        ap.error(str(e))

    protocol = Protocol(args.policy, rho=args.rho, q=args.q, k=args.k,
                        interval=args.interval)
    config = FederationConfig(rounds=args.rounds, batch_size=args.batch,
                              local_steps=args.local_steps,
                              eval_every=args.eval_every,
                              backend=args.backend,
                              delta_graph=args.delta,
                              uplink=args.uplink, downlink=args.downlink,
                              devices=args.devices,
                              selection=args.selection,
                              verbose=True)
    t0 = time.time()
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        if args.clock == "event":
            arrivals = make_arrivals(args, ds.n_clients, args.rounds)
            trigger = make_trigger(args)
            print(f"policy={args.policy} clock=event "
                  f"arrivals={arrivals!r} "
                  f"trigger={trigger!r} dataset={args.dataset} "
                  f"clients={ds.n_clients} config={config}")
            engine = AsyncFederationEngine.build(
                ds, splits, zoo, assignment, protocol, arrivals=arrivals,
                trigger=trigger, config=config, seed=args.seed + 1)
            hist = engine.fit(splits, until=args.until)
        else:
            schedule = make_schedule(args, ds.n_clients, args.rounds)
            print(f"policy={args.policy} "
                  f"schedule={schedule or 'always-on'} "
                  f"dataset={args.dataset} clients={ds.n_clients} "
                  f"config={config}")
            engine = FederationEngine.build(ds, splits, zoo, assignment,
                                            protocol, config=config,
                                            schedule=schedule,
                                            seed=args.seed + 1)
            hist = engine.fit(splits)
    prec, rec = precision_recall(engine.fed, splits, ds.n_classes)
    summary = {
        "policy": args.policy, "dataset": args.dataset,
        "clock": args.clock, "rounds": args.rounds,
        "final_acc": hist.mean_acc[-1], "selected_acc": hist.selected_acc,
        "macro_precision": prec, "macro_recall": rec,
        "virtual_time": hist.times[-1],
        "server_rounds": hist.server_rounds[-1],
        "staleness": hist.staleness[-1],
        "uplink": args.uplink, "downlink": args.downlink,
        "bytes_up": hist.bytes_up[-1], "bytes_down": hist.bytes_down[-1],
        "wall_s": round(time.time() - t0, 1),
    }
    if args.clock == "event":
        summary["arrivals"] = repr(engine.arrivals)
        summary["trigger"] = repr(engine.bus.trigger)
    else:
        summary["schedule"] = args.schedule
    if hist.graph_stats:
        summary["graph"] = hist.graph_stats[-1]
    if args.devices:
        summary["devices"] = args.devices
    if args.selection != "exact":
        summary["selection"] = args.selection
    if args.zoo != "mlp-s,mlp-m,mlp-l":
        summary["zoo"] = args.zoo
    if args.assignment:
        summary["assignment"] = args.assignment
    if args.ckpt:
        from repro.checkpoint import save_federation
        save_federation(args.ckpt, engine.fed, step=args.rounds,
                        bus=engine.bus)
        summary["ckpt"] = f"{args.ckpt}/step_{args.rounds}.msgpack"
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
