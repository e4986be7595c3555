"""tests/streaming drives threads (the engine's, the event loop's, the
handlers'): run it under the omnirace runtime lock checker (see
tests/lockcheck.py)."""

from tests.lockcheck import _runtime_lock_check  # noqa: F401
