"""SQMD core — the paper's contribution as a composable JAX module."""
from repro.core.distill import local_loss, ref_loss, sqmd_grads, sqmd_loss
from repro.core.engine import (AsyncFederationEngine, Federation,
                               FederationConfig, FederationEngine, History,
                               evaluate, precision_recall)
from repro.core.graph import (CollaborationGraph, ddist_graph, fedmd_graph,
                              graph_stats, select_neighbors,
                              selection_matrix)
from repro.core.messenger import cohort_messengers, make_messenger
from repro.core.policies import (DDistPolicy, FedMDPolicy, ISGDPolicy,
                                 SQMDPolicy, ServerPolicy, as_policy,
                                 get_policy, register_policy,
                                 registered_policies)
from repro.core.protocols import Protocol, ddist, fedmd, isgd, sqmd
from repro.core.quality import candidate_mask, quality_scores
from repro.core.runtime import (ClientRuntime, Clock, Event, EveryKUploads,
                                EveryUpload, Quorum, ServerBus, SyncClock,
                                Trigger, WallInterval, as_trigger,
                                get_trigger, register_trigger,
                                registered_triggers)
from repro.core.schedules import (AlwaysOn, ArrivalProcess, BurstyArrivals,
                                  HeterogeneousCadence, RandomDropout,
                                  Schedule, ScheduleArrivals, StagedJoin,
                                  Straggler, StragglerLatency, as_arrivals,
                                  as_schedule, get_arrivals, get_schedule,
                                  register_arrivals, register_schedule,
                                  registered_arrivals, registered_schedules)
from repro.core.server import (ServerState, init_server, policy_round,
                               server_round, staleness_summary,
                               upload_messengers)
from repro.core.similarity import (divergence_matrix, similarity_matrix,
                                   update_divergence_cache)
from repro.core.wire import (Codec, Payload, as_codec, bytes_per_messenger,
                             decode, encode, get_codec, payload_bytes,
                             register_codec, registered_codecs)

__all__ = [
    "local_loss", "ref_loss", "sqmd_grads", "sqmd_loss",
    "Federation", "History", "evaluate", "precision_recall",
    "FederationConfig", "FederationEngine", "AsyncFederationEngine",
    "Clock", "SyncClock", "Event", "ClientRuntime", "ServerBus",
    "Trigger", "EveryUpload", "EveryKUploads", "WallInterval", "Quorum",
    "as_trigger", "get_trigger", "register_trigger", "registered_triggers",
    "ArrivalProcess", "ScheduleArrivals", "StragglerLatency",
    "HeterogeneousCadence", "BurstyArrivals", "as_arrivals", "get_arrivals",
    "register_arrivals", "registered_arrivals", "staleness_summary",
    "CollaborationGraph", "ddist_graph", "fedmd_graph", "graph_stats",
    "select_neighbors", "selection_matrix", "cohort_messengers",
    "make_messenger",
    "Codec", "Payload", "as_codec", "bytes_per_messenger", "decode",
    "encode", "get_codec", "payload_bytes", "register_codec",
    "registered_codecs",
    "Protocol", "ddist", "fedmd", "isgd", "sqmd",
    "ServerPolicy", "SQMDPolicy", "FedMDPolicy", "DDistPolicy",
    "ISGDPolicy", "as_policy", "get_policy", "register_policy",
    "registered_policies",
    "Schedule", "AlwaysOn", "StagedJoin", "RandomDropout", "Straggler",
    "as_schedule", "get_schedule", "register_schedule",
    "registered_schedules",
    "candidate_mask", "quality_scores", "ServerState", "init_server",
    "policy_round", "server_round", "upload_messengers",
    "divergence_matrix", "similarity_matrix", "update_divergence_cache",
]
