"""The device-loop drain ring went with its option (PR 37): gone, not
hidden.  Each verb refuses the flag in argparse, before any boot, and
the engine's constructor refuses the keyword instead of ignoring it."""

import pytest

from flowsentryx_tpu import cli


@pytest.mark.parametrize("verb", ["serve", "audit", "ranges", "cluster"])
def test_device_loop_flag_is_refused_before_any_boot(verb, capsys):
    with pytest.raises(SystemExit) as exc:
        cli.main([verb, "--mega", "auto", "--device-loop", "2"])
    assert exc.value.code == 2
    assert "unrecognized arguments: --device-loop" in capsys.readouterr().err


def test_engine_refuses_the_device_loop_keyword():
    from flowsentryx_tpu.core.config import FsxConfig
    from flowsentryx_tpu.engine import Engine, NullSink, TrafficSource
    from flowsentryx_tpu.engine.traffic import TrafficSpec

    with pytest.raises(TypeError, match="device_loop"):
        Engine(FsxConfig(), TrafficSource(TrafficSpec(), total=256),
               NullSink(), mega_n="auto", device_loop=1)
