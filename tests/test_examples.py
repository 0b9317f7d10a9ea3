"""Every example script must run end-to-end (subprocess smoke)."""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
ENV = {**os.environ, "PYTHONPATH": "src"}


def _run(args, timeout=420):
    r = subprocess.run([sys.executable] + args, capture_output=True,
                       text=True, env=ENV, cwd=str(ROOT), timeout=timeout)
    assert r.returncode == 0, f"{args}\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}"
    return r.stdout


class TestExamples:
    def test_quickstart(self):
        out = _run(["examples/quickstart.py"])
        assert "correct=True" in out and "factor=4" in out

    def test_banking_sweep(self):
        out = _run(["examples/banking_sweep.py"])
        assert "paper 2.40x" in out and "branchy" in out

    def test_compile_to_calyx(self):
        out = _run(["examples/compile_to_calyx.py", "--model", "ffnn",
                    "--factor", "2"])
        assert "cycles=" in out and ".futil" in out

    def test_train_lm_with_failure(self):
        out = _run(["examples/train_lm.py", "--steps", "14",
                    "--inject-failure", "6", "--batch", "4", "--seq", "32"])
        assert "restarts=1" in out and out.strip().endswith("OK")

    def test_serve_batched(self, tmp_path):
        prom = tmp_path / "batched.prom"
        spans = tmp_path / "batched.jsonl"
        out = _run(["examples/serve_batched.py", "--requests", "2",
                    "--gen", "6", "--prompt-len", "8",
                    "--metrics-out", str(prom),
                    "--spans-out", str(spans), "--stable"])
        assert out.strip().endswith("OK")
        assert "serve_tokens_generated_total 12" in prom.read_text()
        self._check_spans(spans, requests=2)

    def test_serve_batched_fault_plan(self, tmp_path):
        # generate a plan via the CLI, then replay it: victim rows must be
        # dropped with the 'fault' reason while the rest of the batch
        # finishes, and the stable span stream must be byte-deterministic
        plan = tmp_path / "plan.json"
        _run(["-m", "repro.launch.faults", "--seed", "3", "--steps", "40",
              "--rate", "0.12", "--slots", "4",
              "--kinds", "nan_logits,inf_logits,cache_corrupt",
              "--out", str(plan)])
        spans = [tmp_path / "chaos_a.jsonl", tmp_path / "chaos_b.jsonl"]
        metrics = tmp_path / "chaos.json"
        for i, sp in enumerate(spans):
            out = _run(["examples/serve_batched.py", "--requests", "4",
                        "--gen", "12", "--prompt-len", "8",
                        "--fault-plan", str(plan),
                        "--spans-out", str(sp), "--stable"]
                       + (["--metrics-out", str(metrics)] if i == 0 else []))
            assert out.strip().endswith("OK")
            assert "resilience: faults injected=" in out
        assert spans[0].read_text() == spans[1].read_text()
        import json
        m = json.loads(metrics.read_text())["metrics"]
        assert m["serve_faults_injected_total"]["value"] > 0
        assert m["serve_faults_detected_total"]["value"] > 0
        assert m["serve_requests_truncated_fault_total"]["value"] \
            == m["serve_requests_truncated_total"]["value"] > 0
        # every row completes exactly once, finished or dropped-for-fault
        sys.path.insert(0, str(ROOT / "src"))
        try:
            from repro.obs import spans as SP
        finally:
            sys.path.pop(0)
        events = SP.from_jsonl(spans[0].read_text())
        assert SP.validate(events) == []
        summaries = SP.summarize(events)
        assert len(summaries) == 4
        reasons = {s.reason for s in summaries.values()}
        assert reasons <= {SP.FINISHED, SP.TRUNCATED_PREFIX + "fault"}
        assert SP.TRUNCATED_PREFIX + "fault" in reasons

    def test_serve_batched_deadline(self, tmp_path):
        # an immediate deadline truncates every row with the 'deadline'
        # reason and no TTFT sample is ever recorded (sentinel regression)
        metrics = tmp_path / "deadline.json"
        spans = tmp_path / "deadline.jsonl"
        out = _run(["examples/serve_batched.py", "--requests", "2",
                    "--gen", "6", "--prompt-len", "8",
                    "--deadline-ms", "0.001",
                    "--metrics-out", str(metrics),
                    "--spans-out", str(spans), "--stable"])
        assert out.strip().endswith("OK")
        import json
        m = json.loads(metrics.read_text())["metrics"]
        assert m["serve_requests_truncated_deadline_total"]["value"] == 2
        assert m["serve_ttft_us"]["count"] == 0
        sys.path.insert(0, str(ROOT / "src"))
        try:
            from repro.obs import spans as SP
        finally:
            sys.path.pop(0)
        events = SP.from_jsonl(spans.read_text())
        assert SP.validate(events) == []
        assert all(s.reason == SP.TRUNCATED_PREFIX + "deadline"
                   for s in SP.summarize(events).values())

    def test_serve_launcher(self, tmp_path):
        metrics = tmp_path / "serve.json"
        spans = tmp_path / "serve.jsonl"
        out = _run(["-m", "repro.launch.serve", "--reduced", "--slots", "2",
                    "--requests", "3", "--gen", "4", "--prompt-len", "4",
                    "--metrics-out", str(metrics),
                    "--spans-out", str(spans), "--stable"])
        assert "3/3 requests" in out
        import json
        doc = json.loads(metrics.read_text())
        m = doc["metrics"]
        assert m["serve_requests_completed_total"]["value"] == 3
        assert m["serve_ttft_us"]["count"] == 3
        self._check_spans(spans, requests=3)

    @staticmethod
    def _check_spans(path, requests):
        sys.path.insert(0, str(ROOT / "src"))
        try:
            from repro.obs import spans as SP
        finally:
            sys.path.pop(0)
        events = SP.from_jsonl(path.read_text())
        assert SP.validate(events) == []
        summaries = SP.summarize(events)
        assert len(summaries) == requests
        assert all(s.reason == SP.FINISHED for s in summaries.values())
