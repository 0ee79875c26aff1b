"""Faults planted under the timed path, for the tests that have to see
`correct` come out false, and for the control runs on the chip. Each is
`fault(node)`, called by the harness once the node is built; `undo()`
takes every patch back."""

from __future__ import annotations

_UNDO: list = []


def _patch(obj, name, new) -> None:
    _UNDO.append((obj, name, getattr(obj, name)))
    setattr(obj, name, new)


def undo() -> None:
    while _UNDO:
        obj, name, old = _UNDO.pop()
        setattr(obj, name, old)


def _verifier_classes():
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.jaxed25519.verify import JAXBatchVerifier

    return CPUBatchVerifier, JAXBatchVerifier


def accept_all(node) -> None:
    """The control: the guarantee that a signature is checked, broken.
    Every backend answers `valid` for every triple."""
    for cls in _verifier_classes():
        _patch(cls, "_verify", lambda self: [True] * len(self._items))


def half_batch(node) -> None:
    """Half of the batch left out: the second half of every batch is
    taken as valid unchecked."""
    for cls in _verifier_classes():
        real = cls._verify

        def _verify(self, real=real):
            items = self._items
            self._items = items[:len(items) // 2]
            try:
                head = real(self)
            finally:
                self._items = items
            return list(head) + [True] * (len(items) - len(head))

        _patch(cls, "_verify", _verify)


def state_unchanged(node) -> None:
    """A step that returns its state unchanged: apply_block applies
    nothing and hands back the state it was given."""
    _patch(node.block_exec, "apply_block",
           lambda state, block_id, block, **kw: state)


def answer_altered(node) -> None:
    """An answer altered where it is produced: the app stores every
    value with one byte appended."""
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication

    real = KVStoreApplication.deliver_tx
    _patch(KVStoreApplication, "deliver_tx",
           lambda self, tx: real(self, tx + b"!"))


FAULTS = {f.__name__: f for f in
          (accept_all, half_batch, state_unchanged, answer_altered)}
