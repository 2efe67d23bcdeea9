"""The program's trace spans and device scopes, by name.

Host spans are ``jax.profiler.TraceAnnotation``s: they land on the
profiler's ``/host:CPU`` plane and record only while a profiler session is
active (on a TPU the device planes' clock runs up to about a millisecond
apart from the host's).  Device phases are ``jax.named_scope``s: they only
set the HLO ``op_name`` metadata of the ops traced inside them, so the
compiled program's text carries each op's phase and the lowered code is
otherwise unchanged.  The benchmark reads both by these names.
"""
from __future__ import annotations

import gc

import jax

span = jax.profiler.TraceAnnotation
scope = jax.named_scope

# Host spans of the training loops (``launch/train.py``), each with ``step``.
BATCH = "train/batch"    # build and place the step's batch
STEP = "train/step"      # dispatch the pod step / train_single's step_fn
EVAL = "train/eval"      # dispatch the gate's test-loss forwards
ROUND = "train/round"    # fold in the round key, dispatch the round
FETCH = "train/fetch"    # wait for device values on the host
LOG = "train/log"        # the log line
CKPT = "train/ckpt"      # checkpoint saves
GC = "host/gc"           # a garbage collection, from ``gc.callbacks``
SPANS = (BATCH, STEP, EVAL, ROUND, FETCH, LOG, CKPT, GC)

# Device phases of the Hermes round (``dist/hermes_sync.py``, ``dist/wire.py``).
GATE = "hermes/gate"        # z-score gates, live mask, admission
ENCODE = "hermes/encode"    # gate-zeroed deltas, encode, error residual
GATHER = "hermes/gather"    # the payload ship across the pod axis
MERGE = "hermes/merge"      # decode and loss-weighted merge
REFRESH = "hermes/refresh"  # pushing pods take the global model
SCOPES = (GATE, ENCODE, GATHER, MERGE, REFRESH)

# Device phases of the training step's expert and latent-attention layers
# (``models/moe.py: moe_dropless``, ``models/attention.py: apply_mla``).
ROUTE = "moe/route"          # router logits, softmax, top-k, balance loss
DISPATCH = "moe/dispatch"    # sort by held expert, gather, scatter-combine
EXPERTS = "moe/experts"      # grouped products over the held experts
SHARED = "moe/shared"        # the shared experts, once per token
MLA = "mla/attend"           # MLA projections, rope, attention, output
STEP_SCOPES = (ROUTE, DISPATCH, EXPERTS, SHARED, MLA)

_open_gc = []


def _gc_span(phase, info):
    if phase == "start":
        if span.is_enabled():
            s = span(GC, generation=info["generation"])
            s.__enter__()
            _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


def install_gc_span() -> None:
    """Put every garbage collection on the trace as a ``host/gc`` span.
    Idempotent; a collection outside a profiler session records nothing."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
