"""Overlap-flag arming (common/backend.py, docs/overlap.md)."""

import os

from horovod_tpu.common import backend


class TestOverlapScheduling:
    """enable_overlap_scheduling (docs/overlap.md): the flags travel in
    LIBTPU_INIT_ARGS — which only libtpu reads — never in XLA_FLAGS,
    where jaxlib aborts on them."""

    def test_cpu_platform_is_noop(self, monkeypatch, capsys):
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
        assert backend.enable_overlap_scheduling("cpu") is False
        assert os.environ["LIBTPU_INIT_ARGS"] == ""  # untouched
        assert "latency hiding" in capsys.readouterr().err

    def test_tpu_platform_arms_flags_before_backend(self, monkeypatch):
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "--existing=1")
        xla_flags = os.environ.get("XLA_FLAGS")
        # Pretend no backend exists yet so the flags can apply.
        monkeypatch.setattr(backend, "_backend_already_created",
                            lambda: False)
        assert backend.enable_overlap_scheduling("tpu") is True
        flags = os.environ["LIBTPU_INIT_ARGS"]
        assert "--existing=1" in flags
        for f in backend._OVERLAP_XLA_FLAGS:
            assert f in flags
        assert os.environ.get("XLA_FLAGS") == xla_flags
        # Idempotent: a second call adds nothing.
        before = os.environ["LIBTPU_INIT_ARGS"]
        assert backend.enable_overlap_scheduling("tpu") is True
        assert os.environ["LIBTPU_INIT_ARGS"] == before

    def test_tpu_after_backend_created_refuses(self, monkeypatch, capsys):
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
        monkeypatch.setattr(backend, "_backend_already_created",
                            lambda: True)
        assert backend.enable_overlap_scheduling("tpu") is False
        assert "already initialized" in capsys.readouterr().err

    def test_auto_arms_without_guessing_the_platform(self, monkeypatch):
        """No device-file or environment heuristics: where no platform is
        pinned the flags are armed, and only a TPU client ever reads
        them."""
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
        monkeypatch.setattr(backend, "_backend_already_created",
                            lambda: False)
        assert backend.enable_overlap_scheduling("auto") is True
        assert backend._OVERLAP_XLA_FLAGS[0] in \
            os.environ["LIBTPU_INIT_ARGS"]

    def test_requested_platform_reads_jax_config(self, monkeypatch):
        # conftest pinned jax_platforms=cpu: the session is headed there.
        assert backend._requested_platform() == "cpu"
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
        assert backend.enable_overlap_scheduling() is False
