"""In-`jit` flight recorder — per-round scalars from inside the scan.

The reference and serve tiers run all K rounds inside one compiled
`dagm_run_chunk` program, so per-round solver health (the Eq. 17b
outer-gap estimate, the penalty term, wire bytes, the realized alive
fraction under faults) is invisible to the host until the run ends.
The flight recorder makes those scalars observable without breaking
the zero-retrace / bit-exactness contracts: a preallocated
`(capacity, len(FIELDS))` f32 device ring buffer plus an int32 write
count ride the chunk carry (an ordinary pytree leaf, so the serve
engine's generic vmap / slot-freeze / checkpoint machinery handles it
untouched), and each scanned round appends one row with a
`lax.dynamic_update_slice` at `count % capacity`.  Pure ops only — no
`io_callback`, no host sync, no shape that depends on data — and the
whole thing is *absent* (not merely empty) when disabled: with
`recorder=None`, `dagm_run_chunk` builds byte-for-byte the same scan
program it always did, which is what keeps the instrumented-off run
bitwise identical (tests/test_obs.py pins both directions).

Field semantics (`FIELDS` order):

  round          global outer-round index — the recorder's cumulative
                 write count, so it keeps counting across chunks and
                 checkpoint restores.
  outer_gap_sq   ‖∇̂F‖² of the Eq. (17b) hyper-gradient estimate (the
                 stationarity gap the paper's Theorem 1 bounds).
  penalty        γₖ · consensus_error(x) — the value of the penalty
                 term driving consensus (0 when a custom metrics_fn
                 does not expose `consensus_x`).
  wire_bytes     cumulative exact wire bytes this trajectory has sent:
                 Σ_channels sends · bytes_per_send, from the traced
                 `ChannelState.sends` counters and the ledger's host-
                 constant per-send byte costs — in-`jit` agreement with
                 the post-run `CommLedger` charge.
  alive_fraction this round's realized / nominal directed links under
                 the fault mask (1.0 on unmasked runs).

`capacity` trades memory for history: writes wrap (oldest rows
overwritten) so a long run keeps its most recent `capacity` rounds;
`recorder_rows` returns the surviving rows oldest-first.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import numpy as np

#: Column order of the flight-row buffer.
FIELDS = ("round", "outer_gap_sq", "penalty", "wire_bytes",
          "alive_fraction")


@dataclasses.dataclass(frozen=True)
class RecorderSpec:
    """Flight-recorder configuration (hashable — safe to close over as
    a jit-static; the device state lives in the carry, not here)."""
    capacity: int = 1024

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(
                f"RecorderSpec.capacity must be >= 1, got "
                f"{self.capacity}")


class FlightBuffer(NamedTuple):
    """The recorder's carry leaf: (capacity, F) rows + write count.

    A NamedTuple, hence a pytree — vmapping the chunk over a serve
    bucket's job axis batches it to (jobs, capacity, F) rows with a
    per-slot count, exactly like the channel states."""
    rows: Any                 # (capacity, len(FIELDS)) f32
    count: Any                # int32 scalar — total writes ever


def recorder_init(spec: RecorderSpec) -> FlightBuffer:
    """Fresh all-zeros buffer (device constants at trace time)."""
    import jax.numpy as jnp
    return FlightBuffer(
        rows=jnp.zeros((spec.capacity, len(FIELDS)), jnp.float32),
        count=jnp.zeros((), jnp.int32))


def recorder_write(rec: FlightBuffer, values: dict) -> FlightBuffer:
    """Append one row (traced; called from the scan body).

    `values` maps field name → traced scalar for every field except
    `round`, which the recorder fills from its own write count."""
    import jax
    import jax.numpy as jnp
    cap = rec.rows.shape[0]
    row = jnp.stack(
        [rec.count.astype(jnp.float32)]
        + [jnp.asarray(values[f], jnp.float32) for f in FIELDS[1:]])
    idx = jnp.mod(rec.count, cap)
    rows = jax.lax.dynamic_update_slice(
        rec.rows, row[None, :], (idx, jnp.zeros((), jnp.int32)))
    return FlightBuffer(rows=rows, count=rec.count + 1)


def flight_values(metrics: dict, cs: dict, gamma, *,
                  bytes_per_send: dict, mask=None,
                  offdiag_valid=None) -> dict:
    """Build one round's field values from what the scan body already
    has in hand (see module docstring for each field's meaning).

    `bytes_per_send` and `offdiag_valid` are *host constants* captured
    at trace time (`wire_constants`); everything data-dependent comes
    from traced operands, so the row costs a handful of scalar flops
    and no extra communication."""
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.float32)
    gap = metrics.get("hypergrad_est_norm_sq", zero)
    cons = metrics.get("consensus_x")
    penalty = zero if cons is None \
        else jnp.asarray(gamma, jnp.float32) * cons
    wire = zero
    for name, st in cs.items():
        bps = bytes_per_send.get(name)
        if bps:
            wire = wire + st.sends.astype(jnp.float32) * float(bps)
    if mask is None or offdiag_valid is None:
        alive = jnp.ones((), jnp.float32)
    else:
        valid = np.asarray(offdiag_valid, np.float32)
        nominal = float(valid.sum())
        alive = (jnp.sum(jnp.asarray(mask, jnp.float32)
                         * jnp.asarray(valid)) / max(nominal, 1.0))
    return {"outer_gap_sq": gap, "penalty": penalty,
            "wire_bytes": wire, "alive_fraction": alive}


def wire_constants(W) -> tuple[dict, "np.ndarray | None"]:
    """Host constants the flight rows need from a MixingOp, captured
    once at trace time: {channel: exact wire bytes per send} from the
    op's ledger, and the (n, k_max) float mask of *real off-diagonal*
    entries in the padded neighbor table (padding slots point at the
    row's own index and must not count toward the alive fraction);
    None when the op has no sparse tables (dense circulant paths —
    those cannot be fault-masked anyway)."""
    bps = {name: ch.bytes_per_send
           for name, ch in W.ledger.channels.items()}
    sp = getattr(W, "sparse", None)
    valid = None
    if sp is not None:
        # stays a numpy host array: the nominal link count must be a
        # Python constant at trace time, not a staged reduction
        valid = (np.asarray(sp.neighbors)
                 != np.arange(sp.n)[:, None]).astype(np.float32)
    return bps, valid


# ---------------------------------------------------------------------------
# Host-side read-out
# ---------------------------------------------------------------------------

def recorder_rows(rec: FlightBuffer) -> np.ndarray:
    """The buffer's surviving rows, oldest-first — (min(count, cap),
    len(FIELDS)) float32 on host.  Call after the run (forces a device
    sync, like any result read)."""
    rows = np.asarray(rec.rows)
    count = int(np.asarray(rec.count))
    cap = rows.shape[0]
    if count <= cap:
        return rows[:count]
    start = count % cap
    return np.concatenate([rows[start:], rows[:start]], axis=0)


def rows_to_dicts(rows: np.ndarray) -> list[dict]:
    """[{field: float}] per row — the shape the JSONL sink
    serializes."""
    return [{f: float(v) for f, v in zip(FIELDS, row)} for row in rows]
