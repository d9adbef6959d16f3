"""Must-trip fixture for C601 (linted under a pretend serve path, e.g.
anomod/serve/fixture.py): reads of scoring-committed state while a
deferred dispatch is still in flight — before the commit barrier."""


class Engine:
    def tick_defer_call(self, served):
        pending = self._stage_pending(served)
        self._dispatch_rounds(pending, defer=True)   # window opens
        alerts = self.alerts_for(0)                  # C601: pre-commit read
        n = len(self._tenant_det)                    # C601: pre-commit read
        self._flight_tick(0.0, served, 0.0)          # C601: pre-commit publish
        self._commit_deferred()
        return alerts, n

    def tick_armed_deferred(self, served, pending):
        self._deferred = {"pending": pending}        # window opens
        doc = self._census_drain()                   # C601: pre-commit drain
        self._commit_deferred()
        return doc
