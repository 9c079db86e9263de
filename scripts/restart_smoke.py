"""Kill-and-resume restart smoke for the serve engine's crash safety.

Protocol (scripts/ci.sh tier 2), run twice:

**Wave engine** —

1. spawn THIS script as a subprocess in --phase crash mode: an engine
   with a checkpoint directory and the deterministic crash hook
   (`crash_after_chunks=2`) runs a 4-job bucket, dies mid-run with
   `SimulatedCrash`, and exits 86 — leaving chunk-boundary checkpoints
   (carry/ledger/channel npz + host-state sidecar) on disk,
2. a FRESH engine pointed at the same directory restores the run
   (stats.restarts == 1), finishes the surviving chunks, and must
   produce final iterates bit-exactly equal to an uninterrupted
   baseline run — byte-for-byte x, y, rounds and per-channel sends,
3. success clears the checkpoint directory.

**Admission loop** — the same kill, mid-admission: an `AdmissionLoop`
with `bucket_width=2` takes 4 submits (2 admitted into the bucket, 2
still queued-but-unadmitted), crashes after chunk 1, and the fresh
loop must recover BOTH halves off the `loop_*.pkl` sidecar — the
in-flight carries and the never-admitted queue entries — then finish
all 4 jobs bit-exactly vs an uncheckpointed baseline loop.

The subprocess boundary is the point: the resumed engine shares no
process state (no compile cache, no Python objects) with the crashed
one — everything it knows came off disk.  The parent only orchestrates
and never touches JAX: the crash phase and the resume phase (restore,
uninterrupted baseline, bitwise comparison) are each a child process,
one at a time, so on a chip each phase can take the device.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

CRASH_EXIT = 86
JOBS = 4
K = 12


def _specs():
    from repro.serve import JobSpec
    from repro.solve import dagm_spec
    cfg = dagm_spec(alpha=0.05, beta=0.1, K=K, M=3, U=2,
                    dihgp="matrix_free", curvature=6.0)
    return [JobSpec("quadratic", {"n": 8, "d1": 4, "d2": 8, "seed": s},
                    cfg, seed=s, job_id=f"job{s}") for s in range(JOBS)]


def _engine(ckpt_dir, **kw):
    from repro.serve import ServeEngine
    return ServeEngine(chunk_rounds=4, max_width=4, hp_mode="traced",
                       checkpoint_dir=ckpt_dir, **kw)


def _loop(ckpt_dir, **kw):
    from repro.serve.admission import AdmissionLoop
    return AdmissionLoop(chunk_rounds=4, max_width=2, bucket_width=2,
                         hp_mode="traced", checkpoint_dir=ckpt_dir,
                         telemetry=False, **kw)


def crash_phase(ckpt_dir: str) -> int:
    """Run until the hook kills chunk 2, then exit CRASH_EXIT."""
    from repro.serve import SimulatedCrash
    eng = _engine(ckpt_dir, crash_after_chunks=2)
    eng.submit(_specs())
    try:
        eng.run()
    except SimulatedCrash:
        return CRASH_EXIT
    print("ERROR: crash hook never fired", file=sys.stderr)
    return 1


def crash_admission_phase(ckpt_dir: str) -> int:
    """Kill the admission loop after chunk 1: jobs 0-1 are in flight,
    jobs 2-3 are still queued and have never touched a bucket."""
    from repro.serve import SimulatedCrash
    loop = _loop(ckpt_dir, checkpoint_every=1, crash_after_chunks=1)
    loop.submit(_specs())
    try:
        loop.pump()
    except SimulatedCrash:
        return CRASH_EXIT
    print("ERROR: admission crash hook never fired", file=sys.stderr)
    return 1


def resume_phase(ckpt_dir: str) -> int:
    """A fresh engine restores the crashed wave run and must match an
    uninterrupted baseline bit-exactly."""
    import numpy as np
    from repro.serve import ServeEngine
    eng = _engine(ckpt_dir)
    results = eng.run()
    assert eng.stats.restarts == 1, \
        f"expected exactly one restart, got {eng.stats.restarts}"
    assert len(results) == JOBS, f"resumed run returned {len(results)}"
    assert not os.listdir(ckpt_dir), \
        "completed run must clear its checkpoints"

    # uninterrupted baseline, clean engine, no checkpoint dir
    base = ServeEngine(chunk_rounds=4, max_width=4, hp_mode="traced")
    base.submit(_specs())
    baseline = {r.job_id: r for r in base.run()}
    for r in results:
        b = baseline[r.job_id]
        assert np.array_equal(r.x, b.x) and np.array_equal(r.y, b.y), \
            f"{r.job_id}: resumed iterates drifted from baseline"
        assert r.rounds == b.rounds and r.sends == b.sends, \
            f"{r.job_id}: rounds/sends mismatch after resume"
    print(f"restart smoke OK: {JOBS} jobs bit-exact after "
          f"kill -> restore -> resume (restarts=1)")
    return 0


def resume_admission_phase(ckpt_dir: str) -> int:
    """A fresh loop recovers the in-flight and the never-admitted jobs
    and must match an uncheckpointed baseline loop bit-exactly."""
    import numpy as np
    from repro.serve.admission import AdmissionLoop
    loop = _loop(ckpt_dir)
    loop._maybe_restore()
    queued = loop.queue.job_ids()
    assert queued == ["job2", "job3"], \
        f"queued-but-unadmitted jobs lost in the crash: {queued}"
    assert loop.stats.restarts == 1, \
        f"expected exactly one restart, got {loop.stats.restarts}"
    loop.pump()
    loop.step()   # idle tick clears the checkpoints
    assert not os.listdir(ckpt_dir), \
        "drained loop must clear its checkpoints"

    base = AdmissionLoop(chunk_rounds=4, max_width=2, bucket_width=2,
                         hp_mode="traced")
    base.submit(_specs())
    baseline = {r.job_id: r for r in base.run()}
    for jid, b in baseline.items():
        r = loop.result(jid)
        assert np.array_equal(np.asarray(r.x), np.asarray(b.x)) \
            and np.array_equal(np.asarray(r.y), np.asarray(b.y)), \
            f"{jid}: resumed iterates drifted from baseline"
        assert r.rounds == b.rounds and r.sends == b.sends, \
            f"{jid}: rounds/sends mismatch after resume"
    print(f"admission restart smoke OK: {JOBS} jobs (2 in flight, "
          f"2 queued-unadmitted) bit-exact after kill -> restore")
    return 0


PHASES = {"crash": crash_phase,
          "crash-admission": crash_admission_phase,
          "resume": resume_phase,
          "resume-admission": resume_admission_phase}


def _spawn(phase: str, ckpt_dir: str) -> int:
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         ckpt_dir],
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(
                 [os.path.join(os.path.dirname(__file__), "..", "src"),
                  os.environ.get("PYTHONPATH", "")])}).returncode


def _crash_then_resume(crash: str, resume: str, prefix: str) -> None:
    ckpt_dir = tempfile.mkdtemp(prefix=prefix)
    rc = _spawn(crash, ckpt_dir)
    if rc != CRASH_EXIT:
        raise RuntimeError(f"{crash} phase exited {rc}, wanted {CRASH_EXIT}")
    left = sorted(os.listdir(ckpt_dir))
    if not left:
        raise RuntimeError(f"crashed {crash} run left no checkpoints")
    print(f"{crash} phase left {len(left)} checkpoint files")
    rc = _spawn(resume, ckpt_dir)
    if rc != 0:
        raise RuntimeError(f"{resume} phase exited {rc}")
    os.rmdir(ckpt_dir)


def main() -> int:
    _crash_then_resume("crash", "resume", "restart_smoke_")
    _crash_then_resume("crash-admission", "resume-admission",
                       "restart_smoke_adm_")
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--phase":
        sys.exit(PHASES[sys.argv[2]](sys.argv[3]))
    sys.exit(main())
