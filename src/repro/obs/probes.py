"""Online sketch estimation-error probes + the run observer.

The planner (PR 2) *predicts* per-table collision error from a zipf
model; nothing in the repo ever measured the realized error of a live
run.  This module closes that loop (DESIGN.md §15):

**Shadow ground-truth probes** (``TableProbe``).  For K sampled rows of a
sketched table — half *hot* (the zipf head, rows 0..K/2−1, where the
paper's heavy-hitter argument lives) and half *cold* (spread through the
tail, where collision noise concentrates) — keep EXACT dense moments as
a (K, d) shadow, updated every step with the same dedup-summed,
touched-rows-only EMA the sparse-rows kernels apply:

    m_p ← β₁·m_p + (1−β₁)·Σ_{ids==p} g        (touched rows only)
    v_p ← β₂·v_p + (1−β₂)·(Σ_{ids==p} g)²

The shadow is O(K·d) state and O(K·k) work per step (K ≈ 16, k = batch
ids) — cheap enough to ride inside the jit'd step.  At each log interval
the observer compares ``store.read(state, rows=probe_ids)`` against the
shadow: the relative L1 gap IS the realized estimation error of the
sketch at those rows.  For a ``DenseStore`` the gap is exactly zero
(pinned by tests/test_obs.py); for an over-compressed sketch it is the
collision error the paper's claim depends on.  Count-min cleaning decays
the sketch but not the shadow, so cleaning bias shows up in the measured
error — by design: the probe reports estimate-vs-intended-EMA, which is
what the optimizer actually consumes.

**Per-table monitors** (``TableMonitor``) bundle the probe with the
store-level ``AuxStore.stats`` gauges (occupancy / saturation /
sign-cancellation / cleaning mass), the error-feedback residual norm,
and the planner's predicted error, emitting one ``table`` record per
log interval with ``*_pred_error`` vs ``*_meas_error`` side by side.

**RunObserver** is the host-side hub the ``Trainer`` drives: it windows
per-step scalars, computes steps/s, and emits ``step``/``table``/
``phase`` records at ``log_every`` boundaries — the only points where
device state is fetched.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.metrics import MetricsWriter
from repro.obs.profiling import PhaseTimer

_TINY = 1e-12


def probe_row_ids(n_rows: int, k: int = 16) -> Tuple[int, ...]:
    """K probe rows: the first ⌈k/2⌉ ids (the zipf head — hot rows) plus
    ⌊k/2⌋ ids geometrically spread through the tail (cold rows).
    Deterministic, so probe selections are comparable across runs."""
    k = max(min(int(k), n_rows), 1)
    n_hot = (k + 1) // 2
    hot = list(range(n_hot))
    n_cold = k - n_hot
    cold: List[int] = []
    if n_cold > 0:
        lo, hi = n_hot, max(n_rows - 1, n_hot)
        pts = np.unique(np.geomspace(lo + 1, hi + 1, num=n_cold * 4)
                        .astype(np.int64) - 1)
        pts = [int(p) for p in pts if p >= n_hot]
        stride = max(len(pts) // n_cold, 1)
        cold = pts[::stride][:n_cold]
        while len(cold) < n_cold:                 # tiny tables: pad forward
            nxt = (cold[-1] + 1) if cold else n_hot
            if nxt >= n_rows:
                break
            cold.append(nxt)
    return tuple(hot + cold)


@dataclasses.dataclass(frozen=True)
class TableProbe:
    """Shadow ground-truth probe for one (n, d) table's moment pair.

    ``update`` is jit-safe (pure jnp) and is called with every step's
    (ids, grad_rows) batch; probe state is a small pytree that rides
    inside the run's opt_state under a ``"probe"`` key (non-moment tags
    replicate under ``sharding.opt_specs_for_state``, so DP runs carry
    the shadow replicated — correct, since it shadows the GLOBAL batch).
    """

    path: str
    probe_ids: Tuple[int, ...]
    b1: float = 0.9
    b2: float = 0.999
    track_first_moment: bool = True

    @classmethod
    def for_table(cls, path: str, n_rows: int, *, k: int = 16,
                  b1: float = 0.9, b2: float = 0.999,
                  track_first_moment: bool = True) -> "TableProbe":
        return cls(path=path, probe_ids=probe_row_ids(n_rows, k), b1=b1,
                   b2=b2, track_first_moment=track_first_moment)

    @property
    def k(self) -> int:
        return len(self.probe_ids)

    def init(self, dim: int):
        import jax.numpy as jnp
        # distinct allocations per slot: donation-safe (a shared zeros
        # buffer would be donated twice by a donating jit'd step)
        zeros = lambda: jnp.zeros((self.k, int(dim)), jnp.float32)  # noqa
        return {"pm": zeros() if self.track_first_moment else None,
                "pv": zeros(),
                "hits": jnp.zeros((self.k,), jnp.int32)}

    def update(self, pstate, ids, grad_rows):
        """One shadow EMA step from a raw (possibly duplicate-carrying)
        (ids, rows) gradient batch — duplicates of a probe id are summed
        first, exactly as the dedup pre-pass sums them for the kernels."""
        import jax.numpy as jnp
        pids = jnp.asarray(self.probe_ids, jnp.int32)
        hit = (ids[None, :] == pids[:, None]).astype(jnp.float32)  # (K, k)
        gsum = hit @ grad_rows.astype(jnp.float32)                 # (K, d)
        touched = (jnp.sum(hit, axis=1) > 0)
        t = touched[:, None].astype(jnp.float32)
        out = dict(pstate)
        if pstate.get("pm") is not None:
            out["pm"] = pstate["pm"] + t * (1.0 - self.b1) \
                * (gsum - pstate["pm"])
        out["pv"] = pstate["pv"] + t * (1.0 - self.b2) \
            * (gsum * gsum - pstate["pv"])
        out["hits"] = pstate["hits"] + touched.astype(jnp.int32)
        return out

    def errors_device(self, pstate, *, m_store=None, m_state=None,
                      v_store=None, v_state=None) -> Dict[str, Any]:
        """The estimation-error comparison as pure jnp — per-moment mean
        relative L1 error of ``store.read`` at the probe rows vs the
        shadow, restricted to rows the stream actually touched, with the
        v error split into hot/cold halves (the heavy-hitter story is
        that hot-row error stays small even when tail error doesn't).
        Jit-safe: ``TableMonitor`` compiles it into its one-call-per-
        boundary collect; rows not yet seen surface as ``nan`` scalars
        (the host side drops non-finite fields)."""
        import jax.numpy as jnp
        pids = jnp.asarray(self.probe_ids, jnp.int32)
        seen = (pstate["hits"] > 0).astype(jnp.float32)
        out: Dict[str, Any] = {"probe_rows_seen": jnp.sum(seen)}

        def rel_err(est, shadow):
            num = jnp.sum(jnp.abs(est.astype(jnp.float32)
                                  - shadow.astype(jnp.float32)), axis=1)
            den = jnp.sum(jnp.abs(shadow.astype(jnp.float32)),
                          axis=1) + _TINY
            return num / den

        def masked_mean(e, mask):
            c = jnp.sum(mask)
            return jnp.where(c > 0,
                             jnp.sum(e * mask) / jnp.maximum(c, 1.0),
                             jnp.nan)

        n_hot = (self.k + 1) // 2
        hot = seen * (jnp.arange(self.k) < n_hot)
        cold = seen * (jnp.arange(self.k) >= n_hot)
        def quant_noise(store, state, shadow):
            """Expected relative-L1 contribution of int8 cell quantization
            at the probe rows — E|SR noise| is scale/4 per cell (uniform
            within ±scale/2), reduced over depth the way the estimator
            reduces (min for the count-min read, median≈mean for the
            signed median).  Feeds the calibrated ``*_error_ratio``
            denominator: a quantized store's measured error contains this
            term ON TOP of collision error, and without it the ratio
            would read as a collision-model miss."""
            spec = getattr(store, "spec", None)
            if spec is None or not getattr(spec, "quantized", False):
                return None
            from repro.core import quantize as qz
            b = spec.family.bucket(pids)
            sc = qz.bucket_scales(state.scales, b, spec.scale_block)
            s_row = (jnp.mean(sc, axis=0) if spec.signed
                     else jnp.min(sc, axis=0))
            num = shadow.shape[1] * s_row / 4.0
            den = jnp.sum(jnp.abs(shadow.astype(jnp.float32)),
                          axis=1) + _TINY
            return masked_mean(num / den, seen)

        if m_store is not None and pstate.get("pm") is not None:
            e = rel_err(m_store.read(m_state, rows=pids), pstate["pm"])
            out["m_meas_error"] = masked_mean(e, seen)
            qn = quant_noise(m_store, m_state, pstate["pm"])
            if qn is not None:
                out["m_quant_noise"] = qn
        if v_store is not None:
            e = rel_err(v_store.read(v_state, rows=pids), pstate["pv"])
            out["v_meas_error"] = masked_mean(e, seen)
            out["v_meas_error_hot"] = masked_mean(e, hot)
            out["v_meas_error_cold"] = masked_mean(e, cold)
            qn = quant_noise(v_store, v_state, pstate["pv"])
            if qn is not None:
                out["v_quant_noise"] = qn
        return out

    def errors(self, pstate, *, m_store=None, m_state=None,
               v_store=None, v_state=None) -> Dict[str, float]:
        """Host-facing form of ``errors_device``: one device fetch, nan
        (not-yet-seen) fields dropped, plus the static probe-row count."""
        import jax
        dev = self.errors_device(pstate, m_store=m_store, m_state=m_state,
                                 v_store=v_store, v_state=v_state)
        host = jax.device_get(dev)
        out: Dict[str, float] = {"probe_rows": int(self.k)}
        for k, v in host.items():
            f = float(np.asarray(v))
            if np.isfinite(f):
                out[k] = int(f) if k == "probe_rows_seen" else f
        return out


def rows_ema_update(store, state, ids, rows_delta, beta: float,
                    *, square: bool = False):
    """One touched-rows EMA step (row ← β·row + (1−β)·Δ) through ANY
    codec — the dedup + masked ``ema_delta`` form the adam_rows kernels
    apply, usable to drive a store with the exact semantics the probe
    shadow replicates (tests + benchmarks).  ``square=True`` squares the
    DEDUP-SUMMED rows (the v-moment semantics: (Σg)², not Σg²), matching
    ``TableProbe``'s shadow exactly even with duplicate ids."""
    import jax.numpy as jnp
    from repro.kernels import dedup
    db = dedup.dedup_rows(ids, rows_delta)
    uids = jnp.where(db.mask > 0, db.unique_ids, 0)
    target = db.rows * db.rows if square else db.rows
    est_old = store.read(state, rows=uids)
    d = (1.0 - beta) * (target - est_old) * db.mask[:, None]
    return store.accumulate(state, d, rows=uids)


def predicted_table_errors(m_store, v_store, n_rows: int, *,
                           alpha: float = 1.1,
                           freqs=None) -> Dict[str, float]:
    """The planner's model error for this table's bound store pair —
    ``plan.error_model`` evaluated at the stores' actual (depth, width)
    — so runs WITHOUT a solved plan still get a predicted-vs-measured
    comparison against the same model the planner would have used."""
    from repro.plan.error_model import (TableStats, countmin_error,
                                       countsketch_error)
    stats = TableStats(alpha=alpha, freqs=freqs)
    out: Dict[str, float] = {}

    def one(store) -> Optional[float]:
        if store is None:
            return None
        if store.kind == "dense":
            return 0.0
        spec = getattr(store, "spec", None)
        if spec is None:
            return None
        fn = countsketch_error if spec.signed else countmin_error
        return float(fn(stats, n_rows, spec.width, spec.depth))

    m_err, v_err = one(m_store), one(v_store)
    if m_err is not None:
        out["m_pred_error"] = m_err
    if v_err is not None:
        out["v_pred_error"] = v_err
    return out


@dataclasses.dataclass
class TableMonitor:
    """Everything the observer emits about ONE table per log interval.

    ``getter`` maps the run's opt_state to this table's state dict with
    keys ``"m"``/``"v"`` (moment states), optional ``"residual"`` (the
    DP error-feedback sketch) and ``"probe"`` (the shadow state).  The
    single-table sparse layout ``{"step", "m", "v", ...}`` is the
    default."""

    path: str
    m_store: Any = None
    v_store: Any = None
    probe: Optional[TableProbe] = None
    predicted: Dict[str, float] = dataclasses.field(default_factory=dict)
    getter: Optional[Callable[[Any], Dict[str, Any]]] = None
    # optional repro.core.cleaning.AsyncCleaner: when its dispatched decay
    # is still in flight at a boundary, the emitted record's
    # ``v_clean_next_removes`` is zeroed host-side (the projected removal
    # is already underway — quoting it would double-count removed mass)
    cleaner: Any = None
    _last_step: int = dataclasses.field(default=0, repr=False)
    _collect_jit: Any = dataclasses.field(default=None, repr=False)
    # double buffer: (step, window_start, async device vector) dispatched
    # at the previous boundary, materialized at the next one
    _pending: Any = dataclasses.field(default=None, repr=False)

    def _states(self, opt_state) -> Dict[str, Any]:
        if self.getter is not None:
            return self.getter(opt_state)
        return opt_state

    def _device_collect(self, st: Dict[str, Any]) -> Dict[str, Any]:
        """Everything device-side in one traced function (jitted on first
        boundary): store stats, residual norm, probe errors — so a log
        boundary costs ONE compiled call + ONE host fetch, not an eager
        op-by-op walk."""
        import jax.numpy as jnp
        payload: Dict[str, Any] = {}
        for slot, store in (("m", self.m_store), ("v", self.v_store)):
            state = st.get(slot)
            if store is None or state is None:
                continue
            for k, v in store.stats(state).items():
                payload[f"{slot}_{k}"] = v
        if st.get("residual") is not None:
            payload["residual_l1"] = jnp.sum(jnp.abs(st["residual"]))
        if self.probe is not None and st.get("probe") is not None:
            payload.update(self.probe.errors_device(
                st["probe"],
                m_store=self.m_store, m_state=st.get("m"),
                v_store=self.v_store, v_state=st.get("v")))
        return payload

    def collect(self, opt_state, step: int) -> Optional[Dict[str, Any]]:
        """Dispatch this boundary's device stats ASYNC and return the
        payload of the PREVIOUS boundary (now guaranteed cheap to fetch).

        Double-buffering keeps the boundary off the device's critical
        path: a synchronous fetch here would first wait for the step's
        own sketch writes to retire, serializing telemetry against
        training.  Instead the stats computation is enqueued behind the
        in-flight step and materialized one boundary later, when it has
        long finished.  Emitted records carry the step they MEASURED
        (the dispatch step), so the one-boundary lag only delays file
        writes, never mislabels them.  Returns ``None`` on the first
        boundary (nothing pending yet); ``flush()`` drains the last one.
        """
        import jax
        import jax.numpy as jnp
        st = self._states(opt_state)
        if self._collect_jit is None:
            # one eager pass fixes the (static) key set, then the jitted
            # form stacks every scalar into ONE vector — a boundary pays
            # a single compiled call and a single host transfer
            keys = tuple(sorted(self._device_collect(st)))

            def stacked(s):
                p = self._device_collect(s)
                return jnp.stack([jnp.asarray(p[k], jnp.float32)
                                  for k in keys])

            self._collect_jit = (keys, jax.jit(stacked))
        _, fn = self._collect_jit
        out = self.flush()
        pending_clean = (self.cleaner is not None
                         and self.cleaner.in_flight())
        self._pending = (int(step), self._last_step, fn(st),
                         pending_clean)
        self._last_step = int(step)
        return out

    def flush(self) -> Optional[Dict[str, Any]]:
        """Materialize the pending boundary's payload (one host fetch),
        or ``None`` when nothing is pending.  Non-finite scalars (probe
        slots not yet touched) are dropped — the schema forbids them."""
        import jax
        if self._pending is None:
            return None
        step, win_start, vec, pending_clean = self._pending
        self._pending = None
        keys, _ = self._collect_jit
        dev = dict(zip(keys, np.asarray(jax.device_get(vec))))
        payload: Dict[str, Any] = {"step": step, "table": self.path}
        for slot, store in (("m", self.m_store), ("v", self.v_store)):
            name = getattr(store, "cell_dtype_name", None)
            if name is not None and name != "float32":
                payload[f"{slot}_cell_dtype"] = name
        if self.probe is not None:
            payload["probe_rows"] = int(self.probe.k)
        for k, v in dev.items():
            f = float(np.asarray(v))
            if np.isfinite(f):
                payload[k] = int(f) if k == "probe_rows_seen" else f
        payload.update(self.predicted)
        # measured / predicted — the re-planning signal: >> 1 means the
        # realized traffic is harder than the plan's zipf model assumed.
        # Quantized cells widen the envelope by the probe's quantization-
        # noise gauge so the ratio stays calibrated at every cell dtype.
        for slot in ("m", "v"):
            pred = payload.get(f"{slot}_pred_error")
            meas = payload.get(f"{slot}_meas_error")
            if pred is not None and meas is not None:
                env = pred + payload.get(f"{slot}_quant_noise", 0.0)
                payload[f"{slot}_error_ratio"] = meas / max(env, _TINY)
        if self.v_store is not None and hasattr(self.v_store,
                                               "cleans_between"):
            payload["cleans_in_window"] = self.v_store.cleans_between(
                win_start, step)
        if pending_clean and "v_clean_next_removes" in payload:
            payload["v_clean_next_removes"] = 0.0
        return payload


class RunObserver:
    """The host-side hub between the training loop and the metrics file.

        obs = RunObserver(writer, monitors=[...], log_every=10)
        ...
        obs.on_step(step, rec, opt_state)   # every step, host scalars
        obs.close(final_state)              # flush the trailing window

    Per-step cost is appending floats the loop already fetched; device
    state is touched only at ``log_every`` boundaries, where the window's
    means, steps/s, each monitor's ``table`` record, and the phase-timer
    drain go out."""

    def __init__(self, writer: MetricsWriter,
                 monitors: Sequence[TableMonitor] = (),
                 log_every: int = 10,
                 phase_timer: Optional[PhaseTimer] = None):
        self.writer = writer
        self.monitors = list(monitors)
        self.log_every = max(int(log_every), 1)
        self.phase_timer = phase_timer
        self._window: List[Dict[str, float]] = []
        self._emitted_at: Optional[int] = None

    def on_step(self, step: int, rec: Dict[str, float],
                opt_state=None) -> None:
        self._window.append(rec)
        if step % self.log_every == 0:
            self._emit(step, opt_state)

    def _emit(self, step: int, opt_state) -> None:
        if not self._window:
            return
        keys = set().union(*(r.keys() for r in self._window)) - {"step"}
        means = {k: float(np.mean([r[k] for r in self._window if k in r]))
                 for k in sorted(keys)}
        wall = means.pop("time_s", 0.0)
        self.writer.write(
            "step", step=int(step),
            steps_per_s=round(1.0 / wall, 4) if wall > 0 else 0.0,
            window=len(self._window), **{
                k: round(v, 8) for k, v in means.items()})
        self._window.clear()
        if opt_state is not None:
            for mon in self.monitors:
                # collect() is double-buffered: it dispatches THIS
                # boundary's stats async and hands back the previous
                # boundary's payload (None on the first boundary)
                rec = mon.collect(opt_state, int(step))
                if rec is not None:
                    self.writer.write("table", **rec)
        if self.phase_timer is not None:
            phases = self.phase_timer.drain()
            if phases:
                self.writer.write("phase", step=int(step), phases=phases)
        self._emitted_at = int(step)

    def close(self, final_step: Optional[int] = None,
              opt_state=None) -> None:
        """Flush a trailing partial window, each monitor's pending
        boundary, and the writer."""
        if self._window and final_step is not None \
                and final_step != self._emitted_at:
            self._emit(final_step, opt_state)
        for mon in self.monitors:
            rec = mon.flush()
            if rec is not None:
                self.writer.write("table", **rec)
        self.writer.close()
