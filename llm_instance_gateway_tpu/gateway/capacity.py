"""Capacity & saturation plane: a sim-calibrated digital twin of the pool.

The KV observatory (gateway/kvobs.py) answers "where is HBM going"; this
module answers the question the roadmap's re-roling autoscaler must ask
first: **how much load can this pool still take, and when does it run
out?**  Three pieces, one ``tick()`` on the proxy's observability cadence:

- **Saturation indices.**  Per pod and per resource, a 0..1 "how close to
  the wall" index fused from the scraped families: KV-block headroom
  (``1 - free/capacity``), decode-batch occupancy (the window mean of the
  ``tpu:decode_batch_occupancy`` histogram), queue pressure
  (waiting over waiting+running), and prefill compute (the fraction of
  wall time the replica spent prefilling, from the
  ``tpu:prefill_seconds`` accumulator delta).  The pool's index per
  resource is the max over pods — saturation is a weakest-link property.

- **The twin and its forecasts.**  The scrape deltas double as
  calibration windows (``sim/calibrate.calibrate_from_observables``):
  with no TPU access the plane fits the simulator's ``LatencyModel`` from
  live traffic (or loads the committed ``TWIN_CALIBRATION.json`` via
  ``--twin-calibration``), then drives the calibrated DES
  (``sim/run.twin_knee_rate``: bisected TTFT-p95 probes) against the
  observed arrival/mix summary to find the pool's **knee rate** — the
  offered load where TTFT p95 crosses the SLO.  Headroom-at-SLO is
  ``(knee - offered)/knee``; the **time-to-breach forecast** projects the
  offered-rate trend (least-squares slope over the recent window, the
  same horizon the SLO burn windows watch) onto the knee.  A forecast
  entering the breach horizon journals a ``capacity_forecast`` event —
  the alarm that must lead the SLO fast-burn alarm (chaos
  ``saturation_ramp`` pins the lead).

- **Drift detection.**  A twin that silently diverged would forecast
  lies, so every tick compares prediction to observation — prefill
  seconds vs ``model.prefill_s(tokens)``, decode step seconds vs
  ``model.decode_s(kv, batch)``, running occupancy vs Little's law — as
  EMA-smoothed relative divergences (``gateway_twin_drift{observable}``).
  Breaching ``--twin-drift-threshold`` for ``drift_enter_ticks``
  journals a ``twin_drift`` event and marks forecasts **untrusted**:
  surfaces keep exporting but say so (``gateway_twin_trusted 0``,
  ``"trusted": false``) instead of lying, and the breach-forecast alarm
  is suppressed until the drift clears.

Mechanics mirror ``gateway/kvobs.py``: provider read outside the lock,
delta/EMA state under it, journal emits after release, exposition via
``render()`` (the ``gateway_capacity_*``/``gateway_twin_*`` families),
JSON via ``debug_payload()`` (``GET /debug/capacity``, the fleet rollup,
fast-burn black-box dumps, ``tools/capacity_report.py``).
"""

from __future__ import annotations

import time
from dataclasses import asdict, dataclass

from llm_instance_gateway_tpu import events as events_mod
from llm_instance_gateway_tpu.lockwitness import witness_lock
from llm_instance_gateway_tpu.tracing import escape_label

# Saturation resources, in render order.
RESOURCES = ("kv", "decode_slots", "queue", "prefill_compute")

# Drift observables, in render order.
DRIFT_OBSERVABLES = ("prefill_s", "decode_step_s", "occupancy")

# Sentinel for "no breach on the current trend" — Prometheus gauges need a
# number; consumers treat negative as "none" (documented in METRICS.md).
NO_BREACH = -1.0


@dataclass(frozen=True)
class CapacityConfig:
    enabled: bool = True
    # Weight of the newest window in the mix/rate/drift EMAs (1.0 = raw).
    ema_alpha: float = 0.5
    # Committed calibration artifact (--twin-calibration); empty =
    # self-calibrate from live scrape windows.
    calibration_path: str = ""
    # Relative-divergence EMA above this for drift_enter_ticks consecutive
    # ticks = drift (--twin-drift-threshold); below for drift_clear_ticks
    # = trusted again.  0.5 = predictions 50% off — far beyond fit
    # residuals, squarely "the model no longer describes this pool".
    drift_threshold: float = 0.5
    drift_enter_ticks: int = 2
    drift_clear_ticks: int = 3
    # Observation-window floor: tick() folds a new window only once this
    # much clock has passed since the last fold (calls in between return
    # immediately, pre-scrape).  Two jobs: (a) window statistics — a 5s
    # obs tick yields too few prefill completions per window for stable
    # least-squares design matrices (rank-deficient fits); 30s windows
    # calibrate cleanly and sit between the Prometheus scrape interval
    # and the SLO engine's 1m burn windows (drift still alarms within
    # 2 windows = 60s, far inside any burn horizon); (b) tick tax — the
    # fold amortizes over min_window_s/obs_tick_s cheap early-returns.
    # 0 = fold every call (chaos and unit tests drive virtual
    # clocks through that).
    min_window_s: float = 30.0
    # Self-calibration: refit from the newest max_fit_windows whenever at
    # least min_fit_windows accumulated, every refit_every_ticks windows
    # (32 windows at the 30s floor = a refit every ~16min — calibration
    # constants move on deploys and mix shifts, not minute scale; the
    # per-window drift EMA below is what watches the twin continuously
    # and is what forces attention long before the next refit).
    min_fit_windows: int = 6
    max_fit_windows: int = 64
    refit_every_ticks: int = 32
    # Knee search cadence (DES probes are ~ms but not free) and bounds.
    forecast_every_ticks: int = 2
    slo_ttft_s: float = 0.5
    probe_duration_s: float = 4.0
    # Assumed decode slots per replica: converts the occupancy FRACTION
    # the histogram exports into the absolute batch regressor the decode
    # fit and the DES probes share.  Wrong absolute values cancel between
    # fit and probe (both use this constant), so forecasts stay honest.
    decode_slots: int = 16
    # Offered-rate trend: least-squares slope over this many windows.
    trend_window: int = 12
    # A finite time-to-breach at or under this journals capacity_forecast.
    breach_horizon_s: float = 600.0


class CapacityPlanner:
    """Thread-safe capacity plane; ``tick()`` runs on the proxy's
    observability cadence (and lazily from ``/debug/capacity``)."""

    def __init__(self, provider, cfg: CapacityConfig | None = None,
                 journal: "events_mod.EventJournal | None" = None,
                 clock=time.time):
        self.provider = provider
        self.cfg = cfg or CapacityConfig()
        self.journal = journal
        self._clock = clock
        self._lock = witness_lock("CapacityPlanner._lock")
        # Cumulative-counter memory for per-window deltas: pod -> the
        # last scrape row (a flat float tuple, _row order).
        self._prev: dict[str, tuple] = {}
        # Self-calibration window buffer (pool-level, newest last).
        self._windows: list[dict] = []
        # The twin.
        self._model = None                        # sim.core.LatencyModel
        self._model_info: dict = {"source": "none"}
        # The PREVIOUS fold's rows, kept raw (with _prev as the newest)
        # so the per-pod saturation view is derived LAZILY at
        # render/debug time (_derive_saturation): the obs tick pays only
        # the pool-window fold, not 4 rounded dicts per pod nobody may
        # read this period.
        self._rows_old: dict[str, tuple] = {}
        self._sat_dt = 0.0
        self._sat_ticks = -1                      # derive cache key
        self._pods: dict[str, dict] = {}
        self._pool_saturation: dict[str, float] = {}
        self._mix: dict[str, float] = {}          # EMA'd arrival/mix summary
        self._forecast: dict = {"knee_rps": 0.0, "offered_rps": 0.0,
                                "headroom_ratio": 1.0,
                                "time_to_breach_s": NO_BREACH,
                                "trusted": False, "breach_alarm": False}
        self._rate_hist: list[tuple[float, float]] = []
        self._drift: dict[str, float] = {}        # observable -> EMA
        self._drift_state = "ok"
        self._drift_over = 0                      # consecutive over-threshold
        self._drift_under = 0                     # consecutive under-threshold
        self.last_tick = 0.0
        self.ticks = 0
        if self.cfg.calibration_path:
            self._load_artifact(self.cfg.calibration_path)

    def _load_artifact(self, path: str) -> None:
        from llm_instance_gateway_tpu.sim import calibrate as cal

        try:
            model, art = cal.load_calibration(path)
        except (OSError, ValueError, KeyError) as e:
            # A bad artifact degrades to self-calibration, loudly.
            self._model_info = {"source": "error", "path": path,
                                "error": str(e)}
            return
        self._model = model
        self._model_info = {"source": "artifact", "path": path,
                            "artifact_source": art.get("source", ""),
                            "residuals": art.get("residuals", {}),
                            "constants": cal.model_to_dict(model)}

    # -- rollup ---------------------------------------------------------------
    def maybe_tick(self, min_interval_s: float = 1.0) -> None:
        """On-demand rollup with a floor between passes — the window
        deltas difference cumulative counters per PASS, so an unthrottled
        debug poller must not collapse every calibration window to its
        own poll period."""
        if self._clock() - self.last_tick >= min_interval_s:
            self.tick()

    # Row layout (flat numeric tuple — the scrape/fold hot path works on
    # indices, not dicts): 0 prefill_s_sum, 1 prefill_count,
    # 2 decode_s_sum, 3 decode_count, 4 occ_sum, 5 occ_count,
    # 6 prefill_tokens, 7 decode_tokens, 8 kv_capacity, 9 kv_free,
    # 10 running, 11 waiting, 12 kv_usage_pct.
    @staticmethod
    def _row(m) -> tuple:
        """One pod's scrape row.  Direct attribute reads (the Metrics
        dataclass always carries the fields); foreign metrics objects
        fall back to the getattr path."""
        prefill_tokens = decode_tokens = 0.0
        at = getattr(m, "adapter_tokens", None)
        if at:
            for key, v in at.items():
                phase = key[2]
                if phase == "prefill":
                    prefill_tokens += v
                elif phase == "decode":
                    decode_tokens += v
        try:
            waiting = m.waiting_queue_size
            if not waiting:
                waiting = m.prefill_queue_size + m.decode_queue_size
            # No float() on the fast path: the parser already delivers
            # numbers, arithmetic downstream is type-agnostic, and `or 0`
            # covers None — 6 calls/pod/fold add up at fleet width.
            return (m.prefill_seconds_sum, m.prefill_seconds_count,
                    m.decode_step_seconds_sum, m.decode_step_seconds_count,
                    m.decode_batch_occupancy_sum,
                    m.decode_batch_occupancy_count,
                    prefill_tokens, decode_tokens,
                    m.kv_tokens_capacity or 0,
                    m.kv_tokens_free or 0,
                    m.running_queue_size or 0, waiting or 0,
                    m.kv_cache_usage_percent or 0)
        except AttributeError:
            return (float(getattr(m, "prefill_seconds_sum", 0) or 0),
                    float(getattr(m, "prefill_seconds_count", 0) or 0),
                    float(getattr(m, "decode_step_seconds_sum", 0) or 0),
                    float(getattr(m, "decode_step_seconds_count", 0) or 0),
                    float(getattr(m, "decode_batch_occupancy_sum", 0) or 0),
                    float(getattr(m, "decode_batch_occupancy_count", 0) or 0),
                    prefill_tokens, decode_tokens,
                    float(getattr(m, "kv_tokens_capacity", 0) or 0),
                    float(getattr(m, "kv_tokens_free", 0) or 0),
                    float(getattr(m, "running_queue_size", 0) or 0),
                    float(getattr(m, "total_queue_size", 0) or 0),
                    float(getattr(m, "kv_cache_usage_percent", 0) or 0))

    def tick(self, now: float | None = None) -> None:
        now = self._clock() if now is None else now
        # Window floor (cfg.min_window_s): between folds the tick is a
        # clock compare — no scrape, no lock.  Unlocked read of
        # last_tick/ticks mirrors maybe_tick (the obs tick is the only
        # writer; a stale read just delays the fold one period).
        if self.ticks and now - self.last_tick < self.cfg.min_window_s:
            return
        pod_metrics = self.provider.all_pod_metrics()
        emits: list[tuple[str, dict]] = []
        with self._lock:
            dt = now - self.last_tick if self.ticks else 0.0
            self.last_tick = now
            self.ticks += 1
            window = self._fold_windows(pod_metrics, dt)
            self._refit(window)
            self._update_drift(window, emits)
            self._update_forecast(window, now, emits)
        for kind, attrs in emits:
            if self.journal is not None:
                self.journal.emit(kind, **attrs)

    # The per-tick movements below run under self._lock (called from tick).
    def _fold_windows(self, pod_metrics, dt: float) -> dict | None:
        """One fused scrape+fold pass: per-pod accumulator deltas
        (clamped per pod, so one replica's counter reset can't push a
        pool sum negative) -> ONE pool-level observation window (the
        calibration/drift input), or None without a usable window (first
        tick, clock stall, no traffic).

        The per-pod saturation view is NOT built here: the raw rows land
        in ``_prev``/``_rows_old`` and ``_derive_saturation``
        materializes the view lazily when render()/debug_payload() ask —
        the obs tick pays only the sums."""
        cfg = self.cfg
        row = self._row
        old = self._prev
        new: dict[str, tuple] = {}
        t_prefill_s = t_prefills = t_decode_s = t_decode_steps = 0.0
        t_occ = t_occs = t_prefill_tokens = t_decode_tokens = 0.0
        kv_used = running = waiting = 0.0
        have_prev = dt > 0
        for pm in pod_metrics:
            name = pm.pod.name
            r = row(pm.metrics)
            new[name] = r
            if have_prev:
                p = old.get(name)
                if p is not None:
                    # No-reset fast path: the monotone counts (1, 3, 5)
                    # and token sums (6, 7 — these also shrink on
                    # adapter-table eviction) only go backwards on a
                    # replica restart, so one compare chain covers all
                    # eight deltas; the per-field clamp runs only for
                    # the pod that actually reset.
                    if (r[1] >= p[1] and r[3] >= p[3] and r[5] >= p[5]
                            and r[6] >= p[6] and r[7] >= p[7]):
                        t_prefill_s += r[0] - p[0]
                        t_prefills += r[1] - p[1]
                        t_decode_s += r[2] - p[2]
                        t_decode_steps += r[3] - p[3]
                        t_occ += r[4] - p[4]
                        t_occs += r[5] - p[5]
                        t_prefill_tokens += r[6] - p[6]
                        t_decode_tokens += r[7] - p[7]
                    else:
                        d = r[0] - p[0]
                        if d > 0.0:
                            t_prefill_s += d
                        d = r[1] - p[1]
                        if d > 0.0:
                            t_prefills += d
                        d = r[2] - p[2]
                        if d > 0.0:
                            t_decode_s += d
                        d = r[3] - p[3]
                        if d > 0.0:
                            t_decode_steps += d
                        d = r[4] - p[4]
                        if d > 0.0:
                            t_occ += d
                        d = r[5] - p[5]
                        if d > 0.0:
                            t_occs += d
                        d = r[6] - p[6]
                        if d > 0.0:
                            t_prefill_tokens += d
                        d = r[7] - p[7]
                        if d > 0.0:
                            t_decode_tokens += d
            used = r[8] - r[9]
            if used > 0.0:
                kv_used += used
            running += r[10]
            waiting += r[11]
        self._prev = new
        self._rows_old = old
        self._sat_dt = dt
        self._sat_ticks = -1  # invalidate the lazy saturation cache

        if dt <= 0 or t_prefills <= 0 or t_decode_steps <= 0:
            return None
        occ_mean = (t_occ / t_occs) if t_occs > 0 else 0.0
        window = {
            "dt_s": dt,
            "n_pods": len(new),
            "offered_rps": t_prefills / dt,
            "prefill_tokens_mean": t_prefill_tokens / t_prefills,
            "prefill_s_mean": t_prefill_s / t_prefills,
            "decode_step_s_mean": t_decode_s / t_decode_steps,
            "batch_mean": occ_mean * cfg.decode_slots,
            "kv_tokens_mean": kv_used / max(1, len(new)),
            "output_tokens_mean": t_decode_tokens / t_prefills,
            "running_mean": running,
        }
        # Arrival/mix EMA — what the DES probes are driven with.
        a = cfg.ema_alpha
        for key in ("offered_rps", "prefill_tokens_mean",
                    "output_tokens_mean"):
            self._mix[key] = (a * window[key]
                             + (1 - a) * self._mix.get(key, window[key]))
        # The window dict IS the calibration record (the fitter reads
        # its five regressor keys and ignores the rest) — append it
        # as-is rather than re-keying a copy every fold.
        self._windows.append(window)
        del self._windows[:-cfg.max_fit_windows]
        return window

    def _refit(self, window: dict | None) -> None:
        """Self-calibration: fit the twin from accumulated scrape windows
        unless a committed artifact was loaded."""
        cfg = self.cfg
        # Bootstrap fast, maintain slow: an unfitted twin retries every
        # min_fit_windows windows (forecasts stay untrusted until it
        # lands); a fitted one refits on the lazy refit_every_ticks
        # cadence — the drift EMA, not the refit, tracks the twin
        # between fits.
        cadence = (min(cfg.min_fit_windows, cfg.refit_every_ticks)
                   if self._model is None else cfg.refit_every_ticks)
        if (self._model_info.get("source") == "artifact"
                or window is None
                or len(self._windows) < cfg.min_fit_windows
                or self.ticks % max(1, cadence) != 0):
            return
        from llm_instance_gateway_tpu.sim import calibrate as cal

        try:
            model, residuals = cal.calibrate_from_observables(
                list(self._windows), min_windows=cfg.min_fit_windows)
        except ValueError as e:
            # Degenerate traffic (no spread) can't identify the constants;
            # keep the previous fit and record why.
            self._model_info.setdefault("last_fit_error", "")
            self._model_info["last_fit_error"] = str(e)
            return
        self._model = model
        self._model_info = {"source": "self", "residuals": residuals,
                            "fit_tick": self.ticks,
                            "constants": cal.model_to_dict(model)}

    def _update_drift(self, window: dict | None, emits: list) -> None:
        """Predicted-vs-observed divergence per observable, EMA'd, with
        enter/clear hysteresis driving the trusted flag."""
        cfg = self.cfg
        if self._model is None or window is None:
            return
        m = self._model
        drift = self._drift
        a = cfg.ema_alpha
        b = 1 - a
        batch_mean = window["batch_mean"]
        pre_pred = m.prefill_s(window["prefill_tokens_mean"])
        dec_pred = m.decode_s(window["kv_tokens_mean"], batch_mean)
        obs = window["prefill_s_mean"]
        div = abs(pre_pred - obs) / max(abs(obs), 1e-6)
        drift["prefill_s"] = a * div + b * drift.get("prefill_s", div)
        obs = window["decode_step_s_mean"]
        div = abs(dec_pred - obs) / max(abs(obs), 1e-6)
        drift["decode_step_s"] = a * div + b * drift.get("decode_step_s",
                                                         div)
        if batch_mean < 0.9 * cfg.decode_slots:
            # Little's law: concurrency = arrival rate x service time.
            # At saturation this open-system prediction is structurally
            # wrong (queueing absorbs the excess arrivals): comparing it
            # would fire a false drift alarm exactly when the breach
            # forecast matters most, so the observable sits out and the
            # service-time ones keep watching.
            pred = window["offered_rps"] * (
                pre_pred + window["output_tokens_mean"] * dec_pred)
            obs = window["running_mean"]
            # Denominator floors at one sequence: running_mean comes
            # from instantaneous integer queue samples, so sub-1
            # concurrency deltas are sampling noise — relative to obs
            # alone an idle pool (obs 0, pred 0.3) reads as infinite
            # divergence and false-fires drift on a perfect twin.
            div = abs(pred - obs) / max(abs(obs), pred, 1.0)
            drift["occupancy"] = a * div + b * drift.get("occupancy", div)
        worst = max(drift.values(), default=0.0)
        if worst > cfg.drift_threshold:
            self._drift_over += 1
            self._drift_under = 0
            if (self._drift_state == "ok"
                    and self._drift_over >= cfg.drift_enter_ticks):
                self._drift_state = "drift"
                emits.append((events_mod.TWIN_DRIFT, {
                    "worst": round(worst, 4),
                    "threshold": cfg.drift_threshold,
                    "drift": {k: round(v, 4)
                              for k, v in self._drift.items()},
                    "tick": self.ticks}))
        else:
            self._drift_under += 1
            self._drift_over = 0
            if (self._drift_state == "drift"
                    and self._drift_under >= cfg.drift_clear_ticks):
                self._drift_state = "ok"

    def _update_forecast(self, window: dict | None, now: float,
                         emits: list) -> None:
        """Knee search (calibrated DES probes) + offered-rate trend ->
        headroom-at-SLO and time-to-breach."""
        cfg = self.cfg
        trusted = self._model is not None and self._drift_state == "ok"
        fc = dict(self._forecast)
        fc["trusted"] = trusted
        if window is not None:
            fc["offered_rps"] = round(self._mix.get("offered_rps", 0.0), 3)
            self._rate_hist.append((now, self._mix["offered_rps"]))
            del self._rate_hist[:-cfg.trend_window]
        if (self._model is not None and window is not None
                and self.ticks % cfg.forecast_every_ticks == 0):
            from llm_instance_gateway_tpu.sim import run as sim_run

            knee = sim_run.twin_knee_rate(
                self._model,
                prompt_mean=max(8.0, self._mix["prefill_tokens_mean"]),
                output_mean=max(4.0, self._mix["output_tokens_mean"]),
                slo_ttft_s=cfg.slo_ttft_s,
                decode_slots=cfg.decode_slots,
                duration_s=cfg.probe_duration_s,
            ) * max(1, window["n_pods"])
            fc["knee_rps"] = round(knee, 3)
        knee = fc.get("knee_rps", 0.0)
        offered = fc.get("offered_rps", 0.0)
        fc["headroom_ratio"] = round(
            max(0.0, (knee - offered) / knee), 4) if knee > 0 else 0.0
        fc["time_to_breach_s"] = NO_BREACH
        if knee > 0 and len(self._rate_hist) >= 3:
            slope = _lsq_slope(self._rate_hist)
            if offered >= knee:
                fc["time_to_breach_s"] = 0.0
            elif slope > 1e-9:
                fc["time_to_breach_s"] = round((knee - offered) / slope, 1)
        breach = (trusted and fc["time_to_breach_s"] != NO_BREACH
                  and fc["time_to_breach_s"] <= cfg.breach_horizon_s)
        if breach and not self._forecast.get("breach_alarm"):
            emits.append((events_mod.CAPACITY_FORECAST, {
                "time_to_breach_s": fc["time_to_breach_s"],
                "knee_rps": knee, "offered_rps": offered,
                "headroom_ratio": fc["headroom_ratio"],
                "tick": self.ticks}))
        fc["breach_alarm"] = breach
        self._forecast = fc

    def _derive_saturation(self) -> None:
        """Materialize the per-pod saturation view from the last two
        scrape rows (idempotent per tick; runs under self._lock).  This
        is the display half of the fold, paid by render()/debug readers
        instead of the obs tick."""
        if self._sat_ticks == self.ticks:
            return
        self._sat_ticks = self.ticks
        old, dt = self._rows_old, self._sat_dt
        pods: dict[str, dict] = {}
        for name, r in self._prev.items():
            occ = pc = 0.0
            if dt > 0:
                p = old.get(name)
                if p is not None:
                    d_occs = r[5] - p[5]
                    if d_occs > 0.0:
                        occ = (r[4] - p[4]) / d_occs
                        if occ < 0.0:
                            occ = 0.0
                    pc = (r[0] - p[0]) / dt
                    pc = 1.0 if pc > 1.0 else (pc if pc > 0.0 else 0.0)
            cap = r[8]
            kv = 1.0 - r[9] / cap if cap > 0.0 else r[12]
            kv = 1.0 if kv > 1.0 else (kv if kv > 0.0 else 0.0)
            wait = r[11]
            run = r[10]
            q = wait / (wait + (run if run > 1.0 else 1.0))
            sat = {"kv": round(kv, 4), "decode_slots": round(occ, 4),
                   "queue": round(q, 4), "prefill_compute": round(pc, 4)}
            pods[name] = {"saturation": sat,
                          "saturation_index": max(sat.values())}
        self._pods = pods
        self._pool_saturation = {
            res: max((p["saturation"][res] for p in pods.values()),
                     default=0.0)
            for res in RESOURCES}

    # -- export ---------------------------------------------------------------
    def render(self) -> list[str]:
        """The ``gateway_capacity_*`` / ``gateway_twin_*`` families."""
        with self._lock:
            self._derive_saturation()
            pods = {n: dict(p["saturation"]) for n, p in self._pods.items()}
            pool = dict(self._pool_saturation)
            fc = dict(self._forecast)
            drift = dict(self._drift)
        lines = []
        if pool:
            lines.append("# TYPE gateway_capacity_saturation gauge")
            for r in RESOURCES:
                lines.append('gateway_capacity_saturation{resource="%s"} %.4f'
                             % (escape_label(r), pool.get(r, 0.0)))
        if pods:
            lines.append("# TYPE gateway_capacity_pod_saturation gauge")
            for name in sorted(pods):
                for r in RESOURCES:
                    lines.append(
                        'gateway_capacity_pod_saturation{pod="%s",'
                        'resource="%s"} %.4f'
                        % (escape_label(name), escape_label(r),
                           pods[name].get(r, 0.0)))
        lines += [
            "# TYPE gateway_capacity_offered_rps gauge",
            "gateway_capacity_offered_rps %.3f" % fc["offered_rps"],
            "# TYPE gateway_capacity_knee_rps gauge",
            "gateway_capacity_knee_rps %.3f" % fc["knee_rps"],
            "# TYPE gateway_capacity_headroom_ratio gauge",
            "gateway_capacity_headroom_ratio %.4f" % fc["headroom_ratio"],
            "# TYPE gateway_capacity_time_to_breach_seconds gauge",
            "gateway_capacity_time_to_breach_seconds %.1f"
            % fc["time_to_breach_s"],
        ]
        if drift:
            lines.append("# TYPE gateway_twin_drift gauge")
            for obs_name in DRIFT_OBSERVABLES:
                if obs_name in drift:
                    lines.append('gateway_twin_drift{observable="%s"} %.4f'
                                 % (escape_label(obs_name), drift[obs_name]))
        lines += [
            "# TYPE gateway_twin_trusted gauge",
            "gateway_twin_trusted %d" % (1 if fc["trusted"] else 0),
        ]
        return lines

    def debug_payload(self) -> dict:
        """The gateway's ``/debug/capacity`` JSON body (also what
        ``tools/capacity_report.py`` and the black-box dump embed)."""
        with self._lock:
            self._derive_saturation()
            return {
                "pods": {n: dict(p) for n, p in sorted(self._pods.items())},
                "saturation": dict(self._pool_saturation),
                "mix": {k: round(v, 3) for k, v in self._mix.items()},
                "forecast": dict(self._forecast),
                "twin": {
                    "model": dict(self._model_info),
                    "drift": {k: round(v, 4)
                              for k, v in self._drift.items()},
                    "state": self._drift_state,
                    "fit_windows": len(self._windows),
                },
                "ticks": self.ticks,
                "last_tick": self.last_tick,
                "config": asdict(self.cfg),
            }


def _lsq_slope(points: list[tuple[float, float]]) -> float:
    """Least-squares slope of (t, rate) points — the offered-load trend."""
    n = len(points)
    mt = sum(t for t, _ in points) / n
    mr = sum(r for _, r in points) / n
    denom = sum((t - mt) ** 2 for t, _ in points)
    if denom <= 0:
        return 0.0
    return sum((t - mt) * (r - mr) for t, r in points) / denom
