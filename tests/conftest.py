import jax
import pytest

# Tests run on the default single-CPU backend (the 512-device override is
# dry-run-only by design). Everything here must be fast and deterministic.
jax.config.update("jax_enable_x64", False)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def assert_watermark_state():
    """Check a facade server's running backlog counts against a scan of
    ``backlog()``, and its size watermark against the scan's decision at
    every depth up to one past the deepest backlog."""

    def check(server):
        queue, inner = server.queue, server.snapshot_server
        backlog = queue.backlog()
        assert queue.pending_total == sum(backlog)
        assert queue.max_backlog() == max(backlog, default=0)
        saved = inner.size_watermark
        try:
            for w in range(1, max(backlog, default=0) + 2):
                inner.size_watermark = w
                want = "size" if any(backlog) and max(backlog) >= w else None
                assert inner._watermark_due() == want
        finally:
            inner.size_watermark = saved

    return check
