"""HDC-as-a-service: the similarity-search backend of the slot ring.

The paper's end state — a wireless-on-chip similarity-search fabric serving
"heavy traffic from millions of users" — maps here onto the same continuous
batching machinery that fronts the LMs (``repro.serving.slotring`` /
``scheduler.SlotScheduler``), with three pieces:

* ``TenantRegistry`` — many classifier *tenants* resident at once. Every
  tenant's prototype bank occupies one row of ONE banked store
  [max_tenants, C, d|W] whose class axis is sharded over ``model`` (each
  tenant's classes live on the same IMC cores). Onboarding/eviction is a
  jitted ``dynamic_update_slice`` of one tenant row — no recompile, the
  serve step never changes shape.
* ``HDCEngine`` — a ``SlotRingEngine`` whose state is per-slot query batches +
  tenant store-rows + RNG keys, and whose step is ONE
  ``scaleout.make_mt_ota_serve`` launch: the full wire path (OTA vote
  collective, guard-bit packing, pluggable PHY channel) runs slot-batched,
  and the per-core search is a single ``hamming_topk_banked`` call whose bank
  axis spans (slot, core[, permuted bank]) via the ``bank_rows`` indirection.
  Unlike LM decode, every slot COMPLETES each step — admission latency is the
  only queueing — so the emission is the (pred, maxsim) pair itself.
* ``HDCScheduler`` — the ``SlotScheduler`` specialization: requests name a
  tenant, admission swaps the query batch into a free slot, and every running
  slot finishes at each step barrier.

``make_mt_ota_serve`` and ``make_ota_serve`` are two views of one serve
builder (`scaleout._build_ota_serve`): the ring step, and its one-slot view.
Per-slot results are bit-identical to ``make_ota_serve`` of that request
against its tenant's codebook with the request's own key, so multi-tenant
batching is purely a throughput/latency optimization — pinned by
tests/test_serving_hdc.py across representations and channels.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import faults, phy
from repro.core import classifier
from repro.core import hypervector as hv
from repro.core.scaleout import ScaleOutConfig, make_mt_ota_serve
from repro.serving import slotring
from repro.serving.scheduler import SlotScheduler


@dataclasses.dataclass
class HDCRequest:
    rid: int
    tenant: Any                  # tenant id (registry key)
    queries: Any                 # [B, S_tx, e_per, d|W]
    key: Any
    t_submit: float


@dataclasses.dataclass
class HDCCompletion:
    rid: int
    tenant: Any
    pred: np.ndarray             # [B] int32 (baseline) or [B, M] (permuted)
    maxsim: np.ndarray
    t_submit: float
    t_admit: float
    t_finish: float
    status: str = "ok"           # "ok" | "evicted" (deadline-expired slot)

    @property
    def latency(self) -> float:
        """Submit-to-finish wall time (includes queueing)."""
        return self.t_finish - self.t_submit


def _store_write(store, protos, row):
    """Overwrite tenant row `row` of the banked store — the onboarding op."""
    return jax.lax.dynamic_update_slice(store, protos[None], (row, 0, 0))


def multicentroid_bank(key, protos: jax.Array, k_c: int, cfg: ScaleOutConfig,
                       **train_kwargs) -> jax.Array:
    """Expand a [C, d|W] codebook into a class-major [C*k_c, d|W] centroid bank.

    The serve fabric is class-count-agnostic — a multi-centroid tenant is just
    a tenant with ``k_c`` banks per class, onboarded into a registry/config
    built with ``n_classes = C * k_c``. Centroids come from
    `classifier.train_multicentroid` (majority-based k-means in packed space);
    the class-major layout means a serve prediction ``p`` maps back to class
    ``p // k_c`` (`centroid_to_class`), and the tie convention is preserved:
    among equidistant centroids the serve picks the lowest flat index, which
    is the lowest (class, centroid) pair. Returns the representation the
    config serves (packed words or unpacked bits)."""
    cents = classifier.train_multicentroid(key, protos, k_c, **train_kwargs)
    c, _, w = cents.shape
    bank = cents.reshape(c * k_c, w)
    if not cfg.packed:
        bank = hv.unpack(bank, cfg.dim).astype(jnp.uint8)
    return bank


def centroid_to_class(pred: jax.Array, k_c: int) -> jax.Array:
    """Map class-major centroid predictions (from `multicentroid_bank`) back
    to class labels. Works elementwise on any shape (baseline [B] or
    permuted [B, M] predictions alike)."""
    return pred // k_c


def _admit_many_impl(state, queries, rows, keys, slots):
    """Scatter K admissions into the slot ring in ONE compiled program.

    `queries`/`keys` arrive as K-tuples and are stacked INSIDE the trace —
    an eager `jnp.stack` before the call costs ~2K dispatches, which at small
    trial batches outweighs the serve step itself."""
    return {
        "queries": state["queries"].at[slots].set(jnp.stack(queries)),
        "row": state["row"].at[slots].set(rows),
        "key": state["key"].at[slots].set(jnp.stack(keys)),
    }


class TenantRegistry:
    """Resident per-tenant prototype banks in one class-sharded store.

    ``store`` is [max_tenants, n_classes, d|W] with the class axis sharded
    over ``model`` (the same placement the one-slot ``make_ota_serve`` gives
    one tenant's codebook). ``onboard``/``evict`` edit one row via a single
    jitted ``dynamic_update_slice`` (row index traced — one compiled program
    for the registry's lifetime); evicted rows keep their stale contents,
    which is safe because no slot maps to them until re-onboarding overwrites
    the row.
    """

    def __init__(self, mesh: Mesh, cfg: ScaleOutConfig, max_tenants: int):
        if max_tenants < 1:
            raise ValueError("max_tenants must be >= 1")
        self.cfg = cfg
        self.max_tenants = max_tenants
        last = cfg.words if cfg.packed else cfg.dim
        dtype = jnp.uint32 if cfg.packed else jnp.uint8
        self.store = jax.device_put(
            jnp.zeros((max_tenants, cfg.n_classes, last), dtype),
            NamedSharding(mesh, P(None, "model", None)),
        )
        self._write = jax.jit(_store_write, donate_argnums=0)
        self.rows: dict[Any, int] = {}
        self._free: list[int] = list(range(max_tenants))

    def onboard(self, tenant_id, protos: jax.Array) -> int:
        """Install a tenant's [C, d|W] prototype bank; returns its store row."""
        if tenant_id in self.rows:
            raise ValueError(f"tenant {tenant_id!r} already onboarded")
        if not self._free:
            raise ValueError(
                f"registry full ({self.max_tenants} tenants); evict first"
            )
        want = self.store.shape[1:]
        if tuple(protos.shape) != want or protos.dtype != self.store.dtype:
            raise ValueError(
                f"prototype bank must be {want} {self.store.dtype}, got "
                f"{tuple(protos.shape)} {protos.dtype}"
            )
        row = self._free.pop(0)
        self.store = self._write(self.store, protos, jnp.int32(row))
        self.rows[tenant_id] = row
        return row

    def evict(self, tenant_id) -> None:
        """Free a tenant's row (contents stay until the row is reused)."""
        if tenant_id not in self.rows:
            raise ValueError(f"tenant {tenant_id!r} not onboarded")
        self._free.append(self.rows.pop(tenant_id))


class HDCEngine(slotring.SlotRingEngine):
    """Slot-ring HDC backend: N resident query batches, one multi-tenant OTA
    serve launch per step.

    State leaves: per-slot query batches [N, B, S_tx, e_per, d|W], tenant
    store-rows [N] and RNG keys [N, 2]. The step is stateless compute — every
    slot completes, emitting its (pred, maxsim) — so the scheduler frees all
    running slots each step. ``params`` for `step` is (store, channel state):
    the live registry store rides in per call, so onboarding between steps
    needs no engine rebuild.
    """

    def __init__(self, mesh: Mesh, cfg: ScaleOutConfig,
                 chan_state: phy.ChannelState, *, num_slots: int,
                 max_tenants: int, batch: int | None = None):
        self.mesh = mesh
        self.cfg = cfg
        self.chan_state = chan_state
        self.batch = cfg.batch if batch is None else batch
        self.registry = TenantRegistry(mesh, cfg, max_tenants)
        self._serve = self._build_serve(cfg)
        self._admit_many_fn = jax.jit(_admit_many_impl)
        model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
        self._qshape = (
            self.batch, model_size, -(-cfg.m_tx // model_size),
            cfg.words if cfg.packed else cfg.dim,
        )
        super().__init__(num_slots)

    def _build_serve(self, cfg: ScaleOutConfig):
        """Build the serve program for ``cfg`` (hook for the adaptive engine,
        which rebuilds under link-controller cfg variants)."""
        return make_mt_ota_serve(self.mesh, cfg)

    @property
    def params(self):
        """(store, channel state) — fetched fresh each step so tenant
        onboarding/eviction between steps is visible without a rebuild."""
        return self.registry.store, self.chan_state

    def init_state(self) -> dict:
        n = self.num_slots
        dtype = jnp.uint32 if self.cfg.packed else jnp.uint8
        return {
            "queries": jnp.zeros((n,) + self._qshape, dtype),
            "row": jnp.zeros((n,), jnp.int32),   # empty slots search row 0;
            #   their garbage results are never collected by the scheduler
            "key": jnp.zeros((n, 2), jnp.uint32),
        }

    def _admit_impl(self, state, queries, row, key, slot):
        return slotring.slot_update(
            state, {"queries": queries, "row": row, "key": key}, slot
        )

    def admit_into_slot(self, state, queries: jax.Array, tenant_id, slot: int,
                        key: jax.Array) -> dict:
        """Swap one request's query batch into `slot`, bound to its tenant's
        current store row."""
        row = self._tenant_row(tenant_id)
        if tuple(queries.shape) != self._qshape:
            raise ValueError(
                f"queries must be {self._qshape}, got {tuple(queries.shape)}"
            )
        return self._admit_fn(
            state, queries, jnp.int32(row), key, jnp.int32(slot)
        )

    def _tenant_row(self, tenant_id) -> int:
        row = self.registry.rows.get(tenant_id)
        if row is None:
            raise ValueError(f"tenant {tenant_id!r} not onboarded")
        return row

    def admit_many(self, state, queries: list, tenant_ids: list,
                   slots: list, keys: list) -> dict:
        """Admit K requests in one compiled scatter (one program per distinct
        K — at most ``num_slots`` programs for the engine's lifetime). A
        per-request ``_admit_fn`` dispatch costs about half a standalone
        serve, so filling 8 slots one-by-one would erase the step's batching
        win; scattering them at once keeps admission at ~1 dispatch/step.
        The scatter's dispatch is the host span ``hdc.admit_scatter``; the
        rest of the call is the host's bookkeeping."""
        rows = np.asarray([self._tenant_row(t) for t in tenant_ids], np.int32)
        for q in queries:
            if tuple(q.shape) != self._qshape:
                raise ValueError(
                    f"queries must be {self._qshape}, got {tuple(q.shape)}"
                )
        slots = np.asarray(slots, np.int32)
        with TraceAnnotation("hdc.admit_scatter"):
            return self._admit_many_fn(state, tuple(queries), rows,
                                       tuple(keys), slots)

    def step(self, params, state):
        store, chan_state = params
        pred, maxsim = self._serve(
            store, state["queries"], state["row"], chan_state, state["key"]
        )
        return state, (pred, maxsim)


@dataclasses.dataclass(frozen=True)
class LinkControllerConfig:
    """Hysteresis knobs for the closed-loop link controller.

    Per-RX actions (cheapest first): ``patience`` consecutive steps with the
    guard-monitor flip-rate estimate above the analytic band trigger an EM
    re-fit of that receiver's decision regions; a re-fit whose refreshed BER is
    STILL above ``quarantine_ber`` (or that failed outright) counts as a *bad*
    re-fit, and ``quarantine_after`` consecutive bad re-fits quarantine the
    core (its classes drop out of the top-1 reduction). Quarantined cores keep
    evolving, being monitored and re-fit; ``release_after`` consecutive re-fits
    landing below ``release_ber`` release them. The bad/good thresholds are
    deliberately split (0.25 vs 0.10 by default) so a core oscillating around
    one threshold cannot flap in and out of quarantine.

    Fleet action: when the quarantined fraction reaches ``drop_frac`` the
    controller degrades the whole link — bundling width drops to ``m_floor``
    (odd; the non-transmitting TXs abstain, shapes unchanged) and, if
    ``alt_collective`` is set, the vote collective switches (e.g.
    ``psum_packed`` -> ``rs_ag``) — and restores the build-time mode once the
    fraction falls back below. Both directions ride the quarantine hysteresis,
    so the fleet mode cannot flap faster than cores enter/leave quarantine.
    """

    patience: int = 2
    band_kwargs: dict | None = None
    quarantine_ber: float = 0.25
    quarantine_after: int = 3
    release_ber: float = 0.10
    release_after: int = 2
    drop_frac: float = 0.25
    m_floor: int = 1
    alt_collective: str | None = None


class LinkController:
    """Host-side closed-loop link adaptation, run at the step barrier.

    Everything here is numpy on already-synced device values (the scheduler's
    ``_collect`` has just blocked on the step's predictions), so the controller
    costs no extra device round-trips and never touches the compiled serve —
    its outputs are a modified process state (re-fit / quarantine masks folded
    in) and an optional fleet-mode flag the engine maps to a prebuilt serve
    variant. Decisions and their step indices accumulate in ``trace`` for the
    benchmark artifact.
    """

    def __init__(self, cfg: LinkControllerConfig, pstate: "phy.ProcessState"):
        self.cfg = cfg
        kw = cfg.band_kwargs or {}
        self.band = np.asarray(phy.monitor_band(pstate, **kw))
        n = self.band.shape[0]
        self._over = np.zeros(n, np.int32)    # consecutive out-of-band steps
        self._bad = np.zeros(n, np.int32)     # consecutive bad re-fits
        self._good = np.zeros(n, np.int32)    # consecutive good re-fits
        self.quarantined = np.zeros(n, bool)
        self.degraded = False
        self.trace: list[dict] = []
        self._t = 0

    @property
    def n_refits(self) -> int:
        return sum(len(e["rows"]) for e in self.trace if e["action"] == "refit")

    def act(self, pstate: "phy.ProcessState"):
        """One barrier decision. Returns (pstate', degraded | None) — the
        second slot is non-None only on the step the fleet mode flips."""
        cfg = self.cfg
        kw = cfg.band_kwargs or {}
        self._t += 1
        est = np.asarray(pstate.est)
        self._over = np.where(est > self.band, self._over + 1, 0)
        refit = self._over >= cfg.patience
        if refit.any():
            pstate = phy.recharacterize(pstate, jnp.asarray(refit))
            # band refresh ONLY for the re-fit rows: a global recompute would
            # fold the live (drifting) BER of every other row into its own
            # band and ratchet the monitor open (see phy.adaptive_rollout)
            self.band = np.where(
                refit, np.asarray(phy.monitor_band(pstate, **kw)), self.band
            )
            self._over[refit] = 0
            self.trace.append({
                "t": self._t, "action": "refit",
                "rows": np.nonzero(refit)[0].tolist(),
            })
            # judge each re-fit: a freshly characterized core whose BER is
            # still bad is physically degraded (fade/interferer), not stale
            ber = np.asarray(pstate.chan.ber)
            valid = np.asarray(pstate.chan.valid)
            bad_now = refit & (~valid | (ber > cfg.quarantine_ber))
            good_now = refit & valid & (ber < cfg.release_ber)
            self._bad = np.where(
                bad_now, self._bad + 1, np.where(refit, 0, self._bad)
            )
            self._good = np.where(
                good_now, self._good + 1, np.where(refit, 0, self._good)
            )
            newq = (~self.quarantined) & (self._bad >= cfg.quarantine_after)
            rel = self.quarantined & (self._good >= cfg.release_after)
            if newq.any() or rel.any():
                self.quarantined = (self.quarantined | newq) & ~rel
                pstate = phy.set_quarantine(
                    pstate, jnp.asarray(self.quarantined)
                )
                if newq.any():
                    self.trace.append({
                        "t": self._t, "action": "quarantine",
                        "rows": np.nonzero(newq)[0].tolist(),
                    })
                if rel.any():
                    self.trace.append({
                        "t": self._t, "action": "release",
                        "rows": np.nonzero(rel)[0].tolist(),
                    })
        frac = float(self.quarantined.mean())
        want = frac >= cfg.drop_frac
        switched = None
        if want != self.degraded:
            self.degraded = switched = want
            self.trace.append({
                "t": self._t,
                "action": "m_drop" if want else "m_restore",
                "quarantined_frac": frac,
            })
        return pstate, switched


class AdaptiveHDCEngine(HDCEngine):
    """HDCEngine over a LIVING channel with a closed-loop link controller.

    The serve program is the process-threading variant of
    ``make_mt_ota_serve``: each step first evolves the channel one tick of
    ``process`` (same schedule for every data shard — the process key is held
    fixed and the time index is folded inside the step), then serves every
    slot against the evolved channel with quarantined cores masked out of the
    top-1 reduction. The evolved process state is staged per step and
    committed at the scheduler's barrier (``on_barrier``), where the
    ``LinkController`` re-fits / quarantines / switches fleet mode; fleet-mode
    switches swap between serve programs prebuilt through ``step_variant``
    keyed on (m_active, collective) — slot state is shape-stable across
    variants, so a switch is a dict lookup, never a recompile or re-admission.

    Needs ``process.guard_dims > 0``: the guard-symbol monitor is the only
    observation channel, so with no guard block the estimates never move and
    the controller never acts (the serve still tracks the evolving channel).
    """

    def __init__(self, mesh: Mesh, cfg: ScaleOutConfig,
                 chan_state: phy.ChannelState, *, process,
                 num_slots: int, max_tenants: int, batch: int | None = None,
                 process_key: jax.Array | None = None,
                 controller: LinkControllerConfig | None = None):
        self.process = process
        self.pstate = process.init(chan_state)
        self.process_key = (jax.random.PRNGKey(0) if process_key is None
                            else process_key)
        self.controller = self._make_controller(controller, self.pstate)
        self._pending: phy.ProcessState | None = None
        super().__init__(mesh, cfg, chan_state, num_slots=num_slots,
                         max_tenants=max_tenants, batch=batch)
        self._variants[(cfg.m_act, cfg.collective)] = self._serve

    def _make_controller(self, controller: LinkControllerConfig | None,
                         pstate: "phy.ProcessState") -> "LinkController":
        """Controller factory — the fault-tolerant engine swaps in its
        `FaultController` here without re-plumbing the constructor."""
        return LinkController(controller or LinkControllerConfig(), pstate)

    def _build_serve(self, cfg: ScaleOutConfig):
        return make_mt_ota_serve(self.mesh, cfg, process=self.process)

    @property
    def params(self):
        """(store, process state) — the evolving pstate replaces the frozen
        channel state of the static engine."""
        return self.registry.store, self.pstate

    def step(self, params, state):
        store, pstate = params
        pred, maxsim, pstate2 = self._serve(
            store, state["queries"], state["row"], pstate, state["key"],
            self.process_key,
        )
        self._pending = pstate2
        return state, (pred, maxsim)

    def on_barrier(self):
        """Commit the step's evolved process state and let the controller act.

        Called by the scheduler right after the step's device sync, so the
        controller reads settled values; any state it rewrites (re-fit
        centroids, quarantine mask) is picked up by the NEXT step through
        ``params``."""
        if self._pending is None:
            return
        self.pstate, self._pending = self._pending, None
        self.pstate, switched = self.controller.act(self.pstate)
        if switched is not None:
            self._apply_fleet_mode(switched)

    def _apply_fleet_mode(self, degraded: bool) -> None:
        cc = self.controller.cfg
        if phy.get_channel(self.cfg.channel).wire != "votes":
            return  # combo wire: no M-drop / vote-collective alternatives
        if degraded:
            m = cc.m_floor if cc.m_floor % 2 == 1 else max(cc.m_floor - 1, 1)
            coll = cc.alt_collective or self.cfg.collective
        else:
            m = self.cfg.m_tx
            coll = self.cfg.collective
        live = dataclasses.replace(
            self.cfg, m_active=None if m == self.cfg.m_tx else m,
            collective=coll,
        )
        self._serve = self.step_variant(
            (live.m_act, live.collective), lambda: self._build_serve(live)
        )
        self.controller.trace.append({
            "t": self.controller._t, "action": "link_mode",
            "m_active": live.m_act, "collective": live.collective,
        })


@dataclasses.dataclass(frozen=True)
class FaultControllerConfig(LinkControllerConfig):
    """`LinkControllerConfig` plus the quarantine→remap promotion knob.

    ``remap_after`` consecutive barriers spent quarantined promote a core
    from the soft path (masked out of the top-1, still monitored, released
    if its link recovers) to the hard path: it is declared DEAD in the
    `faults.FaultState` and its class banks fail over onto healthy
    same-shard cores (`faults.plan_failover`). Promotion is one-way — a
    remapped core's bank is served elsewhere, so releasing it would race
    the failover — which is why ``remap_after`` sits well above
    ``release_after``: only a core the release hysteresis has repeatedly
    failed to rescue is written off.
    """

    remap_after: int = 3


class FaultController(LinkController):
    """`LinkController` that escalates persistent quarantine to failover.

    The soft loop (re-fit → quarantine → release) handles recoverable
    degradation; `promote` runs right after it at each barrier and counts
    the barriers each core has spent quarantined. At ``remap_after`` the
    core is promoted into ``FaultState.dead_rx`` and the shard's serve
    plan is re-dealt host-side — same compiled serve, the remap rides the
    traced ``serve_rows``/``rx_mask`` inputs. Trace action: ``"remap"``.
    """

    def __init__(self, cfg: FaultControllerConfig, pstate: "phy.ProcessState"):
        super().__init__(cfg, pstate)
        self._q_barriers = np.zeros(self.band.shape[0], np.int32)

    def promote(self, fstate: "faults.FaultState",
                cores_per_shard: int) -> "faults.FaultState":
        """One barrier's promotion decision; returns the (possibly re-dealt)
        fault state the NEXT step serves under."""
        self._q_barriers = np.where(
            self.quarantined, self._q_barriers + 1, 0
        ).astype(np.int32)
        newly_dead = (
            (self._q_barriers >= self.cfg.remap_after)
            & ~np.asarray(fstate.dead_rx)
        )
        if not newly_dead.any():
            return fstate
        fstate = faults.inject(
            fstate, dead_rx=np.asarray(fstate.dead_rx) | newly_dead
        )
        fstate = faults.plan_failover(fstate, cores_per_shard)
        self.trace.append({
            "t": self._t, "action": "remap",
            "rows": np.nonzero(newly_dead)[0].tolist(),
        })
        return fstate


class FaultTolerantHDCEngine(AdaptiveHDCEngine):
    """`AdaptiveHDCEngine` that also threads a live `faults.FaultState`.

    The serve program is the process+faults variant of ``make_mt_ota_serve``:
    each step evolves the channel AND the fault state one tick (transient
    vote erasures redraw, wearout accumulates), serves every slot
    erasure-aware with dead cores' banks failed over, and stages both evolved
    states for the barrier. At ``on_barrier`` the `FaultController` first
    runs the soft loop it inherits, then promotes persistently-quarantined
    cores into the fault state (see `FaultController.promote`).

    With the all-healthy state and the ``static`` fault model this engine is
    bit-identical to `AdaptiveHDCEngine` — fault awareness costs nothing
    until faults exist (pinned in tests/test_faults.py).
    """

    def __init__(self, mesh: Mesh, cfg: ScaleOutConfig,
                 chan_state: phy.ChannelState, *, process, fault_model,
                 num_slots: int, max_tenants: int, batch: int | None = None,
                 process_key: jax.Array | None = None,
                 fault_key: jax.Array | None = None,
                 fstate: "faults.FaultState | None" = None,
                 controller: LinkControllerConfig | None = None):
        self.fault_model = fault_model
        model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
        self._cores_per_shard = cfg.n_rx_cores // model_size
        self.fstate = (faults.healthy_for(cfg, model_size)
                       if fstate is None else fstate)
        self.fault_key = (jax.random.PRNGKey(1) if fault_key is None
                          else fault_key)
        self._pending_fstate: "faults.FaultState | None" = None
        super().__init__(mesh, cfg, chan_state, process=process,
                         num_slots=num_slots, max_tenants=max_tenants,
                         batch=batch, process_key=process_key,
                         controller=controller)

    def _make_controller(self, controller, pstate):
        return FaultController(controller or FaultControllerConfig(), pstate)

    def _build_serve(self, cfg: ScaleOutConfig):
        return make_mt_ota_serve(self.mesh, cfg, process=self.process,
                                 faults=self.fault_model)

    def step(self, params, state):
        store, pstate = params
        pred, maxsim, pstate2, fstate2 = self._serve(
            store, state["queries"], state["row"], pstate, state["key"],
            self.process_key, self.fstate, self.fault_key,
        )
        self._pending = pstate2
        self._pending_fstate = fstate2
        return state, (pred, maxsim)

    def on_barrier(self):
        """Commit both evolved states, run the soft loop, then promote."""
        if self._pending_fstate is not None:
            self.fstate, self._pending_fstate = self._pending_fstate, None
        super().on_barrier()
        self.fstate = self.controller.promote(
            self.fstate, self._cores_per_shard
        )


class HDCScheduler(SlotScheduler):
    """Tenant-aware request queue over an ``HDCEngine``.

    Every running slot finishes at each step barrier (an HDC request is one
    launch, not a token loop), so continuous batching here means: free slots
    refill from the age-ordered queue every step, and a step serves however
    many tenants are resident — the single-launch amortization the benchmark
    measures against per-request one-slot serves.
    """

    def __init__(self, engine: HDCEngine,
                 clock: Callable[[], float] = time.monotonic,
                 *, max_slot_steps: int | None = None, max_requeues: int = 1):
        super().__init__(engine, None, clock,
                         max_slot_steps=max_slot_steps,
                         max_requeues=max_requeues)

    def submit(self, tenant_id, queries: jax.Array, *,
               key: jax.Array | None = None) -> int:
        """Queue one trial batch [B, S_tx, e_per, d|W] for `tenant_id`.
        `key` seeds the request's PHY noise stream (default: fold of the rid)."""
        if tenant_id not in self.engine.registry.rows:
            raise ValueError(f"tenant {tenant_id!r} not onboarded")
        rid = self._next_rid
        self._next_rid += 1
        req = HDCRequest(
            rid, tenant_id, queries,
            key if key is not None else jax.random.PRNGKey(rid), self.clock(),
        )
        # one bucket: HDC query batches are shape-uniform by construction
        self.buckets[0].append(req)
        return rid

    def _step_params(self):
        return self.engine.params

    def _fail_eviction(self, slot: int, record):
        """Deadline eviction (an HDC slot completes every step, so this only
        fires if the step loop itself stalls): empty result, status marks it."""
        req, t_admit = record
        return HDCCompletion(
            req.rid, req.tenant, np.zeros((0,), np.int32),
            np.zeros((0,), np.float32), req.t_submit, t_admit, self.clock(),
            status="evicted",
        )

    def _admit_free_slots(self) -> list:
        """Batched admission: every free slot fills from the age-ordered queue
        in ONE ``admit_many`` scatter (overrides the base per-request loop —
        per-request admit dispatches would eat the step's batching win)."""
        batch = []
        while self.free:
            req = self._pop_oldest()
            if req is None:
                break
            # tenant may have been evicted between submit and admission
            if req.tenant not in self.engine.registry.rows:
                raise RuntimeError(
                    f"tenant {req.tenant!r} evicted with request {req.rid} queued"
                )
            batch.append((req, self.free.pop(0)))
        if batch:
            self.state = self.engine.admit_many(
                self.state,
                [r.queries for r, _ in batch],
                [r.tenant for r, _ in batch],
                [s for _, s in batch],
                [r.key for r, _ in batch],
            )
            t_admit = self.clock()
            for req, slot in batch:
                self.running[slot] = (req, t_admit)
        return []

    def _collect(self, emitted) -> list:
        """The step barrier: wait for the step's results and copy them to the
        host (span ``hdc.fetch``), run the engine's barrier hook (span
        ``hdc.barrier``), then build a completion for every running slot."""
        pred, maxsim = emitted
        with TraceAnnotation("hdc.fetch"):
            p = np.asarray(pred)
            s = np.asarray(maxsim)
        with TraceAnnotation("hdc.barrier"):
            self.engine.on_barrier()    # adaptive engines: commit the evolved
            #   process state + run the link controller on settled values
        finished = []
        for slot in sorted(self.running):
            req, t_admit = self.running.pop(slot)
            done = HDCCompletion(
                req.rid, req.tenant, p[slot], s[slot],
                req.t_submit, t_admit, self.clock(),
            )
            self.results[req.rid] = done
            self.free.append(slot)
            finished.append(done)
        return finished
