"""Typed error hierarchy for the sherman_tpu library.

PR 4 started replacing bare ``ValueError``/``RuntimeError`` raises with
typed classes (``PallasUnavailableError``, ``ExchangeLaneError``) so
callers can branch on WHAT failed instead of string-matching messages;
this module finishes the sweep with a single hierarchy every library
raise belongs to.  ``shermanlint`` rule SL003 enforces it: a bare
``raise ValueError(...)`` / ``RuntimeError(...)`` / ``AssertionError``
in ``sherman_tpu/`` is a lint error.

Design rules:

- Every class multiply-inherits the stdlib exception it replaced
  (``ConfigError`` IS a ``ValueError``), so pre-existing
  ``except ValueError`` / ``pytest.raises(RuntimeError)`` callers keep
  working — the sweep is observable only to callers that opt into the
  typed classes.
- ``ShermanError`` is the catch-all root: ``except ShermanError`` traps
  every library-originated failure without also swallowing stdlib
  errors from user code.
- Subsystem-local typed errors that predate this module
  (``JournalCorruptError``, ``CheckpointCorruptError``,
  ``DegradedError``, ``TargetedRepairFailed``,
  ``PallasUnavailableError``, ``ExchangeLaneError``, ``PrepOverflow``)
  — and newer ones following the same pattern
  (``ServeOverloadError``, the serving front door's typed admission
  backpressure in :mod:`sherman_tpu.serve`) — stay defined next to
  the code that raises them; they all inherit ``ShermanError`` so the
  root catch covers them.  This module is import-leaf (stdlib only)
  precisely so they can.
"""

__all__ = [
    "ShermanError",
    "ConfigError",
    "KeyRangeError",
    "DoubleFreeError",
    "ProtocolError",
    "StateError",
    "MultiprocessUnsupportedError",
    "TreeCorruptError",
    "CheckpointFormatError",
    "ReshardError",
    "NativeBuildError",
    "NativeUnavailableError",
]


class ShermanError(Exception):
    """Root of every typed error the library raises."""


class ConfigError(ShermanError, ValueError):
    """A knob, argument, or environment value failed validation —
    including call preconditions ("bulk_load requires an empty tree"),
    malformed env vars, and unknown enum-style strings.  The message
    names the knob/argument and the accepted values."""


class KeyRangeError(ShermanError, ValueError):
    """Request keys fall outside ``[KEY_MIN, KEY_MAX]`` (the fence-key
    sentinels are reserved; see ops/bits.py)."""


class DoubleFreeError(ShermanError, ValueError):
    """A page was returned to the reclaim pool twice — granting it
    again would silently alias two leaves onto one page."""


class ProtocolError(ShermanError, RuntimeError):
    """A wire/lock/SPMD protocol invariant was breached at runtime:
    a host DSM op refused a row, a local-lock hand-over contract broke,
    or replicated drivers diverged across processes.  These indicate a
    bug (ours or the caller's driver), never a transient condition."""


class StateError(ShermanError, RuntimeError):
    """The object is in the wrong state for this call (journal closed,
    reclaim already running, no checkpoint chain started)."""


class MultiprocessUnsupportedError(ShermanError, RuntimeError):
    """A single-process-only feature was invoked on a multihost mesh
    (chaos injection, dirty-row export, RecoveryPlane, delta
    checkpoints)."""


class TreeCorruptError(ShermanError, RuntimeError):
    """Structural validation failed: the pool holds pages that violate
    the B+-tree invariants (validate.py names each violating class)."""


class CheckpointFormatError(ShermanError, RuntimeError):
    """A checkpoint artifact is structurally unusable — wrong build,
    wrong config, missing arrays, incompatible layout.  Distinct from
    :class:`~sherman_tpu.utils.checkpoint.CheckpointCorruptError`
    (content CRC mismatch on an artifact with the right shape)."""


class ReshardError(ShermanError, RuntimeError):
    """A checkpoint could not be repacked onto the target mesh shape
    (non-covering host shards, address overflow, shape mismatch)."""


class NativeBuildError(ShermanError, RuntimeError):
    """The native helper library failed to compile."""


class NativeUnavailableError(ShermanError, RuntimeError):
    """The native helper library is not importable/loadable in this
    environment; callers fall back to the pure-numpy paths."""
