"""The train loop's profiler control (training/driver.py + obs/profile.py):
a request made while the job runs traces exactly the steps asked for and
closes on every exit path; SIGUSR1 is the operator's handle on it."""

import glob
import json
import os
import signal

import pytest

from megatron_llm_tpu.obs import profile
from megatron_llm_tpu.obs.logging import EVENT_LOG
from megatron_llm_tpu.obs.trace import TRAIN_TRACE
from megatron_llm_tpu.training.driver import SIGNAL_TRACE_STEPS, pretrain
from tests.training.test_driver import MockDataset, _cfg


class Hook:
    """The event log's stream: runs ``actions[iteration]`` on the training
    thread when that iteration's log_window event is written."""

    def __init__(self, actions):
        self.actions = actions

    def write(self, line):
        ev = json.loads(line)
        if ev.get("event") == "log_window":
            self.actions.get(ev["iteration"], lambda: None)()

    def flush(self):
        pass


@pytest.fixture
def run(tmp_path):
    def go(actions, train_iters=6, **train):
        cfg = _cfg(tmp_path, train_iters=train_iters, save=None,
                   eval_interval=1000, log_interval=1, **train)
        ds = MockDataset(cfg.model.vocab_size, cfg.train.seq_length)
        EVENT_LOG.configure(stream=Hook(actions))
        TRAIN_TRACE.clear()
        try:
            return pretrain(cfg, ds)
        finally:
            EVENT_LOG.configure(stream=None)
            assert profile.active() is None, "a session outlived the loop"
            assert not TRAIN_TRACE.enabled
    return go


def traces(d):
    return glob.glob(os.path.join(str(d), "plugins", "profile", "*",
                                  "*.xplane.pb"))


def step_annotations(d):
    """step_num of every StepTraceAnnotation("train") in the trace."""
    from jax.profiler import ProfileData

    (path,) = traces(d)
    nums = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "train":
                    nums += [v for k, v in e.stats if k == "step_num"]
    return sorted(nums)


def traced_iterations():
    return sorted({e["args"]["iteration"]
                   for e in TRAIN_TRACE.chrome_trace()["traceEvents"]})


def test_request_from_the_event_log_hook_traces_exactly_n_steps(
        run, tmp_path, capsys):
    out = tmp_path / "on_demand"
    state = run({2: lambda: profile.request_steps(2, str(out))})
    assert int(state.iteration) == 6
    assert "tracing iterations 3..4" in capsys.readouterr().out
    assert step_annotations(out) == [3, 4]
    # the loop's spans were on for those steps only, each with its cause
    assert traced_iterations() == [3, 4]
    names = {e["name"] for e in TRAIN_TRACE.chrome_trace()["traceEvents"]}
    assert {"batch-generator", "train-step", "dispatch", "metrics_fetch",
            "log"} <= names


def test_two_requests_in_one_job_give_two_traces(run, tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    run({1: lambda: profile.request_steps(1, str(a)),
         4: lambda: profile.request_steps(1, str(b))})
    assert step_annotations(a) == [2] and step_annotations(b) == [5]


def test_trace_closes_on_sigterm(run, tmp_path):
    out = tmp_path / "term"
    with pytest.raises(SystemExit) as e:
        run({1: lambda: profile.request_steps(50, str(out)),
             3: lambda: os.kill(os.getpid(), signal.SIGTERM)})
    assert e.value.code == 0
    assert step_annotations(out) == [2, 3]


def test_trace_closes_on_an_exception(run, tmp_path):
    out = tmp_path / "boom"

    def batches(consumed, gbs):
        raise RuntimeError("the input pipeline broke")

    cfg = _cfg(tmp_path, train_iters=4, save=None, eval_interval=1000,
               profile_dir=str(out), profile_step_start=1,
               profile_step_end=3)
    with pytest.raises(RuntimeError, match="input pipeline"):
        pretrain(cfg, None, batch_provider=batches)
    assert profile.active() is None
    # ... and the window's request did not outlive its loop either
    assert profile.take_step_request(1) is None


def test_on_demand_trace_over_skipped_iterations(run, tmp_path, capsys):
    out = tmp_path / "skip"
    state = run({1: lambda: profile.request_steps(2, str(out))},
                train_iters=5, skip_iters=(2, 3))
    assert int(state.iteration) == 5
    text = capsys.readouterr().out
    assert "tracing iterations 2..3" in text and "window complete" in text
    assert traces(out) and step_annotations(out) == []


def test_an_untaken_window_does_not_leak_into_the_next_job(run, tmp_path):
    run({}, train_iters=2, profile_dir=str(tmp_path / "never"),
        profile_step_start=11, profile_step_end=13)
    assert profile.take_step_request(11) is None
    assert not traces(tmp_path / "never")


@pytest.mark.skipif(not hasattr(signal, "SIGUSR1"), reason="no SIGUSR1")
def test_sigusr1_traces_the_next_three_steps(run, tmp_path, capsys):
    out = tmp_path / "usr1"
    run({2: lambda: os.kill(os.getpid(), signal.SIGUSR1)}, train_iters=7,
        profile_dir=str(out), profile_step_start=1000,
        profile_step_end=1001)
    first, last = 3, 3 + SIGNAL_TRACE_STEPS - 1
    assert f"tracing iterations {first}..{last}" in capsys.readouterr().out
    assert step_annotations(out) == list(range(first, last + 1))


@pytest.mark.skipif(not hasattr(signal, "SIGUSR1"), reason="no SIGUSR1")
def test_sigusr1_without_a_profile_dir_is_said_and_ignored(run, capsys):
    state = run({1: lambda: os.kill(os.getpid(), signal.SIGUSR1)},
                train_iters=3)
    assert int(state.iteration) == 3
    assert "SIGUSR1 ignored" in capsys.readouterr().out


def test_a_step_that_recompiles_is_named_in_its_log_window_event(
        tmp_path, capsys):
    """Batch ramp-up changes the step's batch shape once in this run: the
    ``log_window`` event that covers that step carries ``compiles`` with
    the step's program, the steady steps' events carry none
    (obs/compile.py through driver.training_log)."""
    cfg = _cfg(tmp_path, train_iters=6, save=None, eval_interval=1000,
               log_interval=1, rampup_batch_size=(4, 4, 16))
    ds = MockDataset(cfg.model.vocab_size, cfg.train.seq_length)
    EVENT_LOG.clear()
    pretrain(cfg, ds)
    assert "global batch size ramped to 8" in capsys.readouterr().out
    events = {e["iteration"]: e for e in EVENT_LOG.recent(event="log_window")}
    assert sorted(events) == [1, 2, 3, 4, 5, 6]
    # samples 0-15 at a global batch of 4, then 8: iteration 5 is the
    # first with the new shape (the first step's executable was built at
    # set-up, for the dp_grad_collectives reading)
    recompiled = [it for it, e in events.items()
                  if "jit(step)" in e.get("compiles", {})]
    assert recompiled == [5]
    assert events[5]["compiles"]["jit(step)"] == 1
    assert all("compiles" not in events[it] for it in (2, 3, 4, 6))
