"""Deadlock fixture, across a line of inheritance: the base takes lock A
then lock B of ``self``, the class that inherits it constructs both and
takes them in the opposite order. One object, two locks, one cycle — seen
only if the base's ``self._alock`` is the inheritor's."""

import threading


class TransferSide:
    def a_then_b(self):
        with self._alock:
            with self._block:
                pass


class EngineSide(TransferSide):
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()

    def b_then_a(self):
        with self._block:
            with self._alock:
                pass
