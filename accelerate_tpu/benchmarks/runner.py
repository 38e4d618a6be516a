"""Parent-side bench orchestration.

The runner walks the registry's process groups in priority order, asks
the :class:`~.scheduler.DeadlineScheduler` for a runtime budget, launches
one child per group (one spawn + one jax init per shared model config —
the serial spawn/recompile tax that ate r05), and turns whatever comes
back into the output stream:

* every landed record is emitted IMMEDIATELY with ``"provisional": true``
  (a driver wall-clock kill can no longer erase completed measurements);
* a child killed at its budget yields the last fsync'd partial snapshot
  as a ``{"partial": true, "iters_measured": k}`` record;
* a variant that never ran emits ``{"skipped": "deadline", ...}``;
* the consolidated final block re-prints folded records with the
  headline LAST, for the parse-the-last-line driver.

``launch``, ``emit``, ``log`` and the scheduler's clock are injectable so
every path above is unit-testable without subprocesses or wall time.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

from .partial import partial_path, partial_record, read_partial
from .registry import Variant, VariantRegistry
from .scheduler import DeadlineScheduler, Estimates, skip_record


@dataclass
class LaunchResult:
    returncode: int
    stdout: str
    stderr: str
    timed_out: bool = False


class SubprocessLauncher:
    """Spawn one bench child for a member list: ``python -m
    accelerate_tpu.benchmarks --child <members...> --budget S
    --partial-dir D``. The parent's ``timeout=`` is the hard budget
    enforcement (SIGKILL); the child's ``--budget`` only lets it skip
    later members it can see won't fit."""

    def __init__(self, partial_dir: str):
        self.partial_dir = partial_dir

    def __call__(self, members: Sequence[str],
                 budget_s: Optional[float]) -> LaunchResult:
        cmd = [
            sys.executable, "-m", "accelerate_tpu.benchmarks",
            "--child", *members, "--partial-dir", self.partial_dir,
        ]
        timeout = None
        if budget_s is not None and math.isfinite(budget_s):
            timeout = max(1.0, float(budget_s))
            cmd += ["--budget", f"{timeout:.1f}"]
        env = dict(os.environ)
        env["PYTHONUNBUFFERED"] = "1"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ))
        env["PYTHONPATH"] = (
            repo_root + os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH") else repo_root
        )
        try:
            proc = subprocess.run(
                cmd, text=True, capture_output=True, timeout=timeout, env=env,
            )
        except subprocess.TimeoutExpired as exc:
            def _s(x):
                if x is None:
                    return ""
                return x.decode(errors="replace") if isinstance(x, bytes) else x

            return LaunchResult(-9, _s(exc.stdout), _s(exc.stderr),
                                timed_out=True)
        return LaunchResult(proc.returncode, proc.stdout, proc.stderr)


def _oom_line(err: str) -> Optional[str]:
    return next(
        (l.strip() for l in err.splitlines()
         if "RESOURCE_EXHAUSTED" in l or "Ran out of memory" in l),
        None,
    )


#: units where a SMALLER value is the better measurement (times,
#: latencies, overhead percentages); every other unit (tokens/s,
#: tokens/s/chip, speedup "x") improves upward
_LOWER_IS_BETTER_UNITS = frozenset({"s", "ms", "s/token", "%", "pct"})


def parse_baseline_records(text: str) -> dict[str, dict]:
    """Parse one prior bench output into ``{variant: record}``.

    Accepts either the driver's ``BENCH_*.json`` wrapper (``{"n", "cmd",
    "rc", "tail"}`` where ``tail`` holds the JSON-lines stream) or a raw
    JSON-lines stream. The stream prints every record twice on a clean
    run — provisionally at land time, finally in the consolidated block
    — so the LAST line per variant wins and final records (no
    ``provisional`` flag) displace provisional ones."""
    meta: dict = {}
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict) and "tail" in obj:
        meta = {"prev_round": obj.get("n")}
        text = obj.get("tail") or ""
    provisional: dict[str, dict] = {}
    final: dict[str, dict] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        name = rec.get("variant")
        if not name or rec.get("skipped") or rec.get("value") is None:
            continue
        rec.update(meta)
        if rec.get("provisional"):
            provisional[name] = rec
        else:
            final[name] = rec
    return {**provisional, **final}


def load_baseline(path: Optional[str] = None) -> dict[str, dict]:
    """The previous run's records for regression stamping, from an
    explicit ``path`` (``--baseline``). Empty dict without one: there is
    no implicit lookup, so a chip run is never stamped against whatever
    record happens to sit in the working directory."""
    if path is None:
        return {}
    try:
        with open(path) as f:
            return parse_baseline_records(f.read())
    except OSError:
        return {}


class BenchRunner:
    def __init__(
        self,
        registry: VariantRegistry,
        scheduler: DeadlineScheduler,
        estimates: Estimates,
        launch: Callable[[Sequence[str], Optional[float]], LaunchResult],
        *,
        partial_dir: Optional[str] = None,
        emit: Optional[Callable[[str], None]] = None,
        log: Optional[Callable[[str], None]] = None,
        baseline: Optional[dict[str, dict]] = None,
    ):
        self.registry = registry
        self.scheduler = scheduler
        self.estimates = estimates
        self.launch = launch
        self.partial_dir = partial_dir
        self.emit = emit or (lambda s: print(s, flush=True))
        self.log = log or (
            lambda s: print(s, file=sys.stderr, flush=True)
        )
        # {variant: prior record} from the previous round — every landed
        # record passes through _publish, so stamping there covers the
        # provisional stream and the consolidated block alike
        self.baseline = baseline or {}
        self.results: dict[str, dict] = {}
        self.errors: dict[str, str] = {}
        self.skipped: list[dict] = []
        self.oom_reports: dict[str, str] = {}  # variant -> autopsy path

    # ---------------------------------------------------------------- run
    def run(self) -> int:
        groups = self.registry.groups()
        members = {g: [v.name for v in vs] for g, vs in groups}
        variants = {v.name: v for _, vs in groups for v in vs}
        items = [
            (g, sum(self._estimate(v) for v in vs)) for g, vs in groups
        ]
        planned, plan_skips = self.scheduler.plan(items, members=members)
        for sk in plan_skips:
            for name in members[sk["variant"]]:
                self._skip(variants[name], sk["remaining_s"])
        reserved = [sum(p.budget_s for p in planned[i + 1:])
                    for i in range(len(planned))]
        for item, reserved_later in zip(planned, reserved):
            group_members = [variants[n] for n in item.members]
            budget = self.scheduler.grant(item, reserved_later_s=reserved_later)
            if budget is None:
                for v in group_members:
                    self._skip(v, self.scheduler.deadline.remaining())
                continue
            self._run_group(group_members, budget)
        self._fold()
        self._final_block()
        self.estimates.save()
        headline = self.registry.headline
        return 0 if headline in self.results else 1

    # ------------------------------------------------------------ helpers
    def _estimate(self, v: Variant) -> float:
        return self.estimates.estimate(v.name, v.default_estimate_s)

    def _skip(self, v: Variant, remaining_s: float) -> None:
        rec = skip_record(v.name, self._estimate(v), remaining_s)
        self.skipped.append(rec)
        self.emit(json.dumps(rec))

    def _stamp_trend(self, name: str, rec: dict) -> None:
        """Run-to-run trend: attach the previous round's value and flag
        a >10% degradation of the variant's metric. Partial records are
        stamped with ``prev_*`` but never flagged — a budget-killed
        measurement is not evidence of a regression."""
        prev = self.baseline.get(name)
        if prev is None or rec.get("value") is None:
            return
        rec["prev_value"] = prev.get("value")
        if prev.get("prev_round") is not None:
            rec["prev_round"] = prev["prev_round"]
        prev_value = prev.get("value")
        if not prev_value or rec.get("partial"):
            return
        unit = rec.get("unit") or prev.get("unit") or ""
        change = (float(rec["value"]) - float(prev_value)) / float(prev_value)
        rec["prev_delta_pct"] = round(100.0 * change, 2)
        degraded = (
            change > 0.10 if unit in _LOWER_IS_BETTER_UNITS
            else change < -0.10
        )
        if degraded:
            rec["regression"] = True
            self.log(
                f"REGRESSION: {name} {rec.get('metric')} "
                f"{prev_value} -> {rec['value']} {unit} "
                f"({rec['prev_delta_pct']:+.1f}%)"
            )

    def _publish(self, name: str, rec: dict) -> None:
        rec.setdefault("variant", name)
        self._stamp_trend(name, rec)
        self.results[name] = rec
        # Emit the record the moment the variant lands, flushed, so a
        # driver wall-clock kill cannot discard completed measurements
        # (BENCH_r05 was rc=124 with an empty tail). The consolidated
        # block at the end re-prints the FINAL (folded) records with the
        # headline last — consumers of the whole stream skip provisional
        # lines, the parse-the-last-line driver never sees them on a
        # clean run.
        self.emit(json.dumps({**rec, "provisional": True}))
        extra = rec.get("extra", {})
        if not rec.get("partial") and "variant_wall_s" in extra:
            # feed the cost model: round n+1 schedules against this
            self.estimates.observe(
                name, extra["variant_wall_s"],
                step_time_s=extra.get("step_time_s"),
                compile_time_s=extra.get("compile_time_s"),
            )

    def _fail(self, name: str, err: str) -> None:
        self.errors[name] = err
        self.log(f"bench variant {name} failed (provisional): {err[:160]}")

    def _parse(self, stdout: str) -> tuple[dict[str, dict], dict[str, dict]]:
        """Split the child's JSON lines into (final records, child-side
        skip records), keyed by variant name."""
        recs: dict[str, dict] = {}
        skips: dict[str, dict] = {}
        for line in stdout.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            name = obj.get("variant")
            if not name:
                continue
            if obj.get("skipped"):
                skips[name] = obj
            else:
                recs[name] = obj
        return recs, skips

    def _harvest_partial(self, v: Variant, reason: str) -> bool:
        """Turn the child's last fsync'd snapshot into a published
        partial record. True when something usable was recovered."""
        if not self.partial_dir:
            return False
        snap = read_partial(partial_path(self.partial_dir, v.name))
        rec = partial_record(snap, reason=reason) if snap else None
        if rec is None:
            return False
        self._publish(v.name, rec)
        self.log(
            f"variant {v.name} killed at its budget; recovered partial "
            f"result at iters_measured={rec['iters_measured']}"
        )
        return True

    def _harvest_oom_autopsy(self, crashed: list[Variant]) -> None:
        """An OOM child wrote its ``oom-report.json`` autopsy next to the
        partial snapshots before dying; surface it in the stream so the
        expected-OOM variants (``longseq_xla``) leave a machine-readable
        artifact instead of just a stderr line."""
        if not self.partial_dir:
            return
        try:
            from ..profiling.oom import OOM_REPORT_NAME, read_oom_report
        except Exception:  # noqa: BLE001 — forensics stay best-effort
            return
        report = read_oom_report(self.partial_dir)
        if report is None:
            return
        path = os.path.join(self.partial_dir, OOM_REPORT_NAME)
        for v in crashed:
            self.oom_reports[v.name] = path
            self.emit(json.dumps({
                "variant": v.name,
                "oom_report": path,
                "oom_context": report.get("context"),
                "oom_requested_bytes": report.get("requested_bytes"),
            }))
        self.log(f"OOM autopsy recovered: {path}")

    # --------------------------------------------------------- group loop
    def _run_group(self, group_members: list[Variant],
                   budget_s: float) -> None:
        """One launch per group; whatever it reports is the result. A slow
        number is a number and a crash is an error — nothing is re-run."""
        res = self.launch([v.name for v in group_members], budget_s)
        recs, child_skips = self._parse(res.stdout)
        crashed: list[Variant] = []
        for v in group_members:
            if v.name in child_skips:
                self.skipped.append(child_skips[v.name])
                self.emit(json.dumps(child_skips[v.name]))
            elif v.name in recs:
                self._publish(v.name, recs[v.name])
            elif res.timed_out:
                if not self._harvest_partial(v, reason="budget"):
                    self._fail(v.name, f"timeout after {budget_s:.0f}s")
            else:
                crashed.append(v)
        if crashed:
            err = (res.stderr or "no output").strip()
            oom = _oom_line(err)
            if oom:
                self._harvest_oom_autopsy(crashed)
            for v in crashed:
                self._fail(v.name, oom or err[-300:] or "no output")

    # ------------------------------------------------------------ folding
    def _fold(self) -> None:
        results, errors = self.results, self.errors
        # fold the load-time helper into the decode line (never the
        # reverse: a failed load leaves the decode headline intact with
        # load_s null)
        if "decode" in results:
            extra = results["decode"]["extra"]
            if "decode_load" in results:
                rec_l = results.pop("decode_load")
                extra["load_s"] = rec_l["value"]
                le = rec_l["extra"]
                extra["load_disk_to_host_s"] = le.get("disk_to_host_s")
                extra["load_host_to_device_s"] = le.get("host_to_device_s")
                extra["load_gib"] = le.get("gib")
                extra["load_ref_s"] = 8.7
                if "note" in le:
                    extra["load_note"] = le["note"]
                if rec_l.get("partial"):
                    extra["load_partial"] = True
            elif "decode_load" in errors:
                extra["load_s"] = None
                extra["load_error"] = errors.pop("decode_load")[:160]
            elif any(s["variant"] == "decode_load" for s in self.skipped):
                extra["load_s"] = None
                extra["load_skipped"] = "deadline"

        helpers = ("longseq_xla", "longseq4k", "longseq_xla4k")
        if "longseq" in results:
            extra = results["longseq"]["extra"]
            if "longseq_xla" in results:
                xla_step = results["longseq_xla"]["extra"]["step_time_s"]
                extra["xla_step_time_s"] = xla_step
                extra["flash_speedup_vs_xla"] = round(
                    xla_step / extra["step_time_s"], 3
                )
            else:
                # numeric fields stay numeric (None) for machine
                # consumers; the error text gets its own key
                extra["xla_step_time_s"] = None
                extra["flash_speedup_vs_xla"] = None
                if "longseq_xla" in errors:
                    extra["xla_error"] = errors.pop("longseq_xla")[:160]
                if "longseq_xla" in self.oom_reports:
                    # the expected-OOM comparison point: its autopsy IS
                    # the artifact (requested bytes + ledger + census)
                    extra["xla_oom_report"] = self.oom_reports["longseq_xla"]
            # the S=4096 pair, where dense attention fits 16G: always
            # record whichever step times landed (even a lone one — never
            # discard a valid measurement), and let the pair supply the
            # headline speedup when the S=8192 dense point failed (null
            # in rounds 2 and 3)
            if "longseq4k" in results:
                extra["flash_step_s_s4096"] = (
                    results["longseq4k"]["extra"]["step_time_s"]
                )
            if "longseq_xla4k" in results:
                extra["xla_step_s_s4096"] = (
                    results["longseq_xla4k"]["extra"]["step_time_s"]
                )
            if "longseq4k" in results and "longseq_xla4k" in results:
                flash4k = results["longseq4k"]["extra"]["step_time_s"]
                xla4k = results["longseq_xla4k"]["extra"]["step_time_s"]
                if extra["flash_speedup_vs_xla"] is None:
                    extra["flash_speedup_vs_xla"] = round(xla4k / flash4k, 3)
                    extra["speedup_measured_at_seq"] = 4096
                    extra["speedup_optimizer"] = "sgd"
            for name in helpers:
                results.pop(name, None)
        # when longseq itself failed, measured helper records stay in
        # ``results`` and print as their own lines — a valid measurement
        # is never silently discarded

    def _final_block(self) -> None:
        headline = self.registry.headline
        order = [n for n in self.results if n != headline]
        if headline in self.results:
            order.append(headline)
        for name in order:
            self.emit(json.dumps(self.results[name]))
        for name, err in self.errors.items():
            qualifier = (
                " (expected on 16G chips — the dense-attention comparison"
                " point)"
                if name == "longseq_xla" else ""
            )
            self.log(f"bench variant {name} failed{qualifier}: {err}")
        if self.skipped:
            self.log(
                "skipped (deadline): "
                + ", ".join(sorted({s["variant"] for s in self.skipped}))
            )
