"""The agents of the nodes that run none.

A cell runs a few real ``bin.node`` agents; the rest of its nodes are
registered placeholders.  An agent consumes its exclusive orders at
their second (its claim deletes the order key), and the scheduler
counts an order that is still in the store as load on its node — so a
placeholder whose orders only pile up reads as ever more loaded, and
the scheduler sends a group's exclusive fires to the group's live
members instead (seen in this benchmark's first runs: 1,700-2,100 live
executions where a least-loaded placement over the whole fleet gives
1,200).  This thread does for every placeholder what its agent's claim
would: it watches the orders, remembers each, and deletes it once its
second has come.  It runs nothing and takes no fence: what it has seen
is where the scheduler placed the fire, which is all the comparison
asks of a node that has no agent.
"""

import threading
import time


class Placeholders:
    TICK_S = 0.1

    def __init__(self, store, ks, live_ids: list):
        self.ks = ks
        self.conn = store.clone()
        self.skip = set(live_ids) | {ks.BROADCAST}
        self.pending = {}           # order key -> (second, value)
        self.consumed = {}          # order key -> value
        self.watches_lost = 0
        self._stop = threading.Event()
        self._watch = self.conn.watch(ks.dispatch)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="placeholders")
        self._thread.start()

    def _take(self, ev):
        if ev.type != "PUT":
            return
        parts = ev.kv.key[len(self.ks.dispatch):].split("/")
        if len(parts) < 2 or parts[0] in self.skip:
            return
        head = self.ks.split_bundle_epoch(parts[1])
        if head is not None:
            self.pending[ev.kv.key] = (head[0], ev.kv.value)

    def _run(self):
        while not self._stop.is_set():
            try:
                ev = self._watch.get(timeout=self.TICK_S)
                if ev is not None:
                    self._take(ev)
                    for ev in self._watch.drain():
                        self._take(ev)
            except Exception:  # noqa: BLE001 — WatchLost, or the link
                # what the lost stretch held stays in the store and is
                # read back with whatever else is left there
                self.watches_lost += 1
                if self._stop.wait(0.2):
                    break
                try:
                    self._watch = self.conn.watch(self.ks.dispatch)
                except Exception:  # noqa: BLE001 — next round
                    pass
                continue
            now = time.time()
            due = [k for k, (sec, _v) in self.pending.items() if sec <= now]
            if due:
                try:
                    self.conn.delete_many(due)
                except Exception:  # noqa: BLE001 — they stay pending
                    continue
                for k in due:
                    self.consumed[k] = self.pending.pop(k)[1]

    def stop(self) -> dict:
        """{order key: value} of every order consumed."""
        if not self._stop.is_set():
            self._stop.set()
            self._thread.join(10)
            self.conn.close()
        return self.consumed
