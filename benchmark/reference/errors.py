"""Exception types for the CRDT core."""


class PeritextError(Exception):
    """Base class for framework errors."""


class CausalityError(PeritextError):
    """A change arrived before its causal dependencies were satisfied
    (reference raises RangeError, src/micromerge.ts:894-902).  Delivery layers
    catch this and requeue the change (test/merge.ts:4-23)."""


class IndexOutOfBounds(PeritextError, IndexError):
    """A list index was outside the visible sequence
    (reference RangeError, src/micromerge.ts:1380)."""


class MissingObject(PeritextError):
    """An operation referenced an object that does not exist."""
