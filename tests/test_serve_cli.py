"""Exit status of the serving launcher: a run that did not serve every
request as asked exits 1, whatever the containment tier absorbed."""
import pytest

from repro.launch import serve
from repro.serving.ingress import AsyncIngress
from repro.serving.router import RouterService

DSL = """
SIGNAL embedding math { candidates: ["integral derivative algebra"] }
ROUTE math_route { PRIORITY 200 WHEN embedding("math") MODEL "backend-math" }
BACKEND backend-math { arch: "internlm2-1.8b" }
GLOBAL { default_model: "backend-math" }
"""


@pytest.fixture
def argv(tmp_path):
    path = tmp_path / "policy.dsl"
    path.write_text(DSL)
    return ["--config", str(path), "--ingress", "--slots", "2",
            "--new-tokens", "2", "--requests", "solve the integral",
            "what is algebra"]


def _cancel_on_submit(monkeypatch):
    submit = AsyncIngress.submit

    def cancelling(self, *a, **kw):
        ticket = submit(self, *a, **kw)
        ticket.cancel()
        return ticket
    monkeypatch.setattr(AsyncIngress, "submit", cancelling)


def _crash_first_step(monkeypatch):
    step = RouterService.serve_step
    calls = []

    def crashing(self, *a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("device step refused")
        return step(self, *a, **kw)
    monkeypatch.setattr(RouterService, "serve_step", crashing)


@pytest.mark.parametrize("fault", [None, "cancel", "crash"])
def test_ingress_exit_status(argv, monkeypatch, fault):
    if fault == "cancel":
        _cancel_on_submit(monkeypatch)
    elif fault == "crash":
        _crash_first_step(monkeypatch)
    if fault is None:
        reqs = serve.main(argv)
        assert len(reqs) == 2 and all(not r.failed for r in reqs)
        return
    with pytest.raises(SystemExit) as exc:
        serve.main(argv)
    assert exc.value.code == 1


def test_timeout_flag_admits_timed_out_tickets(argv, monkeypatch):
    """--timeout-s makes an expired ticket an outcome the operator
    asked for; a cancelled one stays a failure under the same flag."""
    reqs = serve.main(argv + ["--timeout-s", "1e-9"])
    assert reqs and all(r.timed_out for r in reqs)
    _cancel_on_submit(monkeypatch)
    with pytest.raises(SystemExit) as exc:
        serve.main(argv + ["--timeout-s", "30"])
    assert exc.value.code == 1
