"""``repro.launch.serve`` smoke: the gtrac mode serves through ``run_queue``
with each of its serving features on, at a tiny reduced width."""

import pytest

from repro.launch import serve

TINY = ["--arch", "gpt2-large", "--reduced", "--tokens", "2",
        "--requests", "3", "--prompt-len", "6"]


@pytest.mark.parametrize("extra", [
    ["--hedged"],
    ["--disaggregate", "--long-fraction", "0.5", "--long-prompt-len", "12",
     "--prefill-chunk", "4"],
    ["--gossip"],
], ids=["hedged", "disaggregate", "gossip"])
def test_gtrac_mode_serves_and_reports_ssr(extra, monkeypatch, capsys):
    # the persistent compile cache is a program's start-up choice, not
    # the test process's
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    assert serve.main(TINY + extra) is None
    out = capsys.readouterr().out
    (ssr,) = [ln for ln in out.splitlines() if ln.startswith("SSR: ")]
    assert ssr.split()[1].endswith("/3")
    assert "completion: " in out
    if "--disaggregate" in extra:
        assert "(disaggregated)" in out
        assert "prefill chunks: 0 " not in out
    if "--gossip" in extra:
        assert "gossip: " in out
