"""The wall-clock driver: offer a phase's queries to the service through
``submit`` / ``tick`` and record when each answer became visible, on the
host's clock.

Nothing here reads the service's own clock or latencies: a query's time
runs from the instant the driver submits it to the instant the driver
sees its answer, so a long tick counts against the service.  Where the
mix carries a feed, the driver offers its weight-update batches between
ticks on the same clock, and records when each became visible: the
first tick that returned with the service's epoch at or past the one
the batch brings.
"""

from __future__ import annotations

import collections
import contextlib
import time

clock = time.perf_counter


class Query:
    """One offered query and what became of it."""

    __slots__ = ("s", "t", "k", "phase", "submit", "complete",
                 "epoch_sub", "epoch_done", "result", "rejected")

    def __init__(self, s, t, k, phase):
        self.s, self.t, self.k = int(s), int(t), int(k)
        self.phase = phase
        self.submit = self.complete = None
        self.epoch_sub = self.epoch_done = None
        self.result = None
        self.rejected = None


class Update:
    """One offered weight-update batch: when it was due, when the driver
    offered it (the first pause between ticks after it fell due), the
    epoch it brings, and when that epoch became visible."""

    __slots__ = ("eids", "new_w", "due", "offered", "epoch", "visible")

    def __init__(self, eids, new_w, due):
        self.eids, self.new_w, self.due = eids, new_w, due
        self.offered = self.epoch = self.visible = None


class Window:
    """What a run measured, handed to every metric reader.

    ``queries`` holds every offered query (warm-up included) in the order
    offered, ``updates`` every offered update batch, ``t_end`` the
    instant the drain ended; ``spans(name)`` the program's spans that
    started in the window, when a traced run recorded them;
    ``snap_open``/``snap_close`` the service's counters at the window's
    edges; ``trace`` the reduced device trace of a traced run
    (``xtrace.DeviceTrace``) or None; ``correct_ids`` the ids of the
    queries whose answers the reference check passed.
    """

    def __init__(self, seconds, drain_s):
        self.seconds = float(seconds)
        self.drain_s = float(drain_s)
        self.t_open = self.t_close = self.t_end = None
        self.setup_s = None
        self.queries = []
        self.updates = []
        self.snap_open = self.snap_close = None
        self.collector = None
        self.trace = None
        self.correct_ids = set()
        self.compiles = {}  # compile events counted inside the window

    def in_window(self, t):
        return t is not None and self.t_open <= t < self.t_close

    def completed_in_window(self):
        return [q for q in self.queries if self.in_window(q.complete)]

    def to_judge(self):
        """Every query answered in the window or drained after it, and
        every one never answered."""
        return [q for q in self.queries
                if q.complete is None or q.complete >= self.t_open]

    def spans(self, name):
        if self.collector is None:
            return None
        return [r for r in self.collector.spans(name)
                if self.t_open <= r.ts < self.t_close]


class Offer:
    """Submits to the service and watches what comes back."""

    def __init__(self, svc, api, win, annotate):
        self.svc, self.api, self.win = svc, api, win
        self.annotate = annotate
        self.open = {}  # service qid -> Query
        self.unseen = collections.deque()  # offered, not yet visible
        self.epoch0 = svc.epoch

    def query(self, q, now):
        q.submit = now
        q.epoch_sub = self.svc.epoch
        self.win.queries.append(q)
        with self.annotate("submit"):
            try:
                tk = self.svc.submit(self.api.QueryRequest(q.s, q.t, q.k))
            except self.api.AdmissionError as e:
                q.rejected = e.reason
                return
        self.open[tk.qid] = q

    def update(self, u, now):
        """Offer one batch; the service applies it at a later tick.  The
        program gets copies: the harness's own stay as drawn."""
        u.offered = now
        u.epoch = self.epoch0 + len(self.win.updates) + 1
        self.win.updates.append(u)
        self.unseen.append(u)
        with self.annotate("update"):
            self.svc.update(self.api.UpdateBatch(u.eids.copy(),
                                                 u.new_w.copy()), wait=False)

    def tick(self):
        with self.annotate("tick"):
            done = self.svc.tick()
        now = clock()
        epoch = self.svc.epoch
        while self.unseen and self.unseen[0].epoch <= epoch:
            self.unseen.popleft().visible = now
        finished = []
        for tk in done:
            q = self.open.pop(tk.qid, None)
            if q is None:
                continue
            q.complete, q.epoch_done = now, epoch
            q.result = tk.result
            finished.append(q)
        return finished


def feed_schedule(feeds, t_open, warmup_s):
    """(due instant, road ids, new weights) of every batch of the
    warm-up and window feeds (``traffic.Feed``), in the order due: the
    warm-up feed opens with the warm-up and its batches all fall due
    before the window's, which opens with the window."""
    out = collections.deque()
    for feed, t0 in zip(feeds or (), (t_open - warmup_s, t_open)):
        out.extend((t0 + feed.due(i), *feed.batch(i))
                   for i in range(len(feed)))
    return out


def closed_loop(svc, api, win, warm, main, clients, warmup_s, hooks,
                feeds=None):
    """``clients`` callers, each sending its next query the moment its
    last one is answered: warm-up queries until the window opens, then
    the window's own list.  With ``feeds`` (the warm-up's and the
    window's ``traffic.Feed``), each batch is offered between ticks once
    it is due, up to the window's close.  Queries still in flight at the
    close, and batches offered but not yet visible, are drained until
    ``win.drain_s`` after it; the queries are checked, but not
    counted."""
    off = Offer(svc, api, win, hooks.annotate)
    t_open = clock() + warmup_s
    src = {"warmup": [warm, 0], "window": [main, 0]}
    due = feed_schedule(feeds, t_open, warmup_s)

    def send(now):
        tag = "warmup" if win.t_open is None else "window"
        ph, i = src[tag]
        if i >= len(ph.s):
            raise RuntimeError(f"closed loop ran out of {tag} queries; "
                               f"raise max_queries in the mix")
        src[tag][1] = i + 1
        off.query(Query(ph.s[i], ph.t[i], ph.k[i], tag), now)

    now = clock()
    for _ in range(clients):
        send(now)
    while True:
        now = clock()
        if win.t_open is None and now >= t_open:
            win.t_open = t_open
            win.t_close = t_open + win.seconds
            win.snap_open = svc.snapshot()
            hooks.window_open(win)
        if win.t_open is not None and now >= win.t_close:
            break
        hooks.poll(now)
        while due and due[0][0] <= now:
            t_due, eids, new_w = due.popleft()
            off.update(Update(eids, new_w, t_due), clock())
        for _ in off.tick():
            if win.t_open is None or clock() < win.t_close:
                send(clock())
    win.snap_close = svc.snapshot()
    hooks.window_close(win)
    deadline = win.t_close + win.drain_s
    while (off.open or off.unseen) and clock() < deadline:
        off.tick()
        hooks.poll(clock())
    win.t_end = clock()
    return off


class NoHooks:
    """The untraced run: no annotations, nothing at the window's edges."""

    def annotate(self, name):
        return contextlib.nullcontext()

    def window_open(self, win):
        pass

    def window_close(self, win):
        pass

    def poll(self, now):
        pass
