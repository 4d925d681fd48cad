"""Names of the serving engine's host spans and of the model step's
device scopes, on the clock of JAX's profiler.

A host span is a ``jax.profiler.TraceAnnotation``: it writes an event on
the profiler's host plane while the profiler runs and does nothing
otherwise, so it needs no switch. The spans nest: every other span lies
inside ``engine.step``, ``sched.dispatch`` inside ``engine.admit``,
``engine.first_token_sync`` inside ``engine.prefill`` and
``sched.complete`` inside ``engine.retire``. None is opened per slot or
per token. The scheduler (``core/``) stays free of JAX: its spans open
at the engine's call sites.

A device scope is a ``jax.named_scope`` inside the paged model step
(``models/transformer.py``); it names the HLO operations it encloses.

JAX is imported when the first span opens, so that importing
``repro.obs`` pulls in no JAX.
"""

from __future__ import annotations

# --- host spans (one TraceAnnotation each) ------------------------------
ENGINE_STEP = "engine.step"                  # all of ServingEngine.step
ENGINE_ADMIT = "engine.admit"                # the admission loop
SCHED_DISPATCH = "sched.dispatch"            # DriftScheduler.dispatch
ENGINE_PREFILL = "engine.prefill"            # one device prefill launch
FIRST_TOKEN_SYNC = "engine.first_token_sync"  # first token read back
DECODE_PREP = "engine.decode_prep"           # lens, tables, uploads, rng
DECODE_LAUNCH = "engine.decode_launch"       # dispatch of the decode step
DECODE_SYNC = "engine.decode_sync"           # sampled tokens read back
ENGINE_RETIRE = "engine.retire"              # per-slot bookkeeping, gauges
SCHED_COMPLETE = "sched.complete"            # DriftScheduler.complete

SPAN_NAMES = frozenset({
    ENGINE_STEP, ENGINE_ADMIT, SCHED_DISPATCH, ENGINE_PREFILL,
    FIRST_TOKEN_SYNC, DECODE_PREP, DECODE_LAUNCH, DECODE_SYNC,
    ENGINE_RETIRE, SCHED_COMPLETE,
})

# --- device scopes of one layer of the paged model step ----------------
QKV = "qkv"                    # norm, Q/K/V projections, RoPE
ATTENTION = "attention"        # the fused Pallas kernel
KV_WRITE = "kv_write"          # the new K/V scattered into the pool
OUT_PROJ_MLP = "out_proj_mlp"  # output projection, norm, MLP
HEAD = "head"                  # final norm and unembedding (once a step)

DEVICE_SCOPES = (QKV, ATTENTION, KV_WRITE, OUT_PROJ_MLP, HEAD)


def span(name: str):
    """A context manager that records ``name`` on the profiler's host
    plane while the profiler runs."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)
