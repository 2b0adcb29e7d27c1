"""The runner of a configuration of kind ``serve``: a served model behind
the fabric's own entry, driven by a closed-loop mix.

``run.py`` finds this file by the configuration's ``kind`` and calls
:func:`run`; a configuration of another kind brings a runner of its own
(``benchmark/runners/<kind>.py``) and edits nothing here. What a runner
hands back: ``window`` (the two readings that open and close it, and
``setup_s``), ``records`` (one per request sent in the window), ``traced``
(the rank's reduction of the device trace, or {}), ``device``,
``attempted``, ``failed`` and the numbers ``compared`` with their ``limits``.
"""

from __future__ import annotations

import os
import random
import threading
import time

import bench_traffic
import run as R

DRAIN_S = 60.0                       # a late first token is late, not wrong


class Window:
    """Offers the mix to ``svc.generate`` from the start of the ramp until
    the window closes, and keeps one record per request. The window opens
    and closes on a reading of the engine's counters between two decode
    blocks (``svc.mark``): the rate is the tokens counted between the two
    readings over the time between them."""

    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float, svc):
        if mix["kind"] != "closed":
            raise R.BenchFailure(f"the serve runner drives closed-loop mixes, "
                                 f"not kind {mix['kind']!r}")
        self.mix, self.svc, self.seconds = mix, svc, float(seconds)
        self.deck = bench_traffic.Deck(mix, seed, vocab)
        self.records = []
        self._lock = threading.Lock()
        self._closed = threading.Event()
        self.open = self.close = None

    def _one(self, req: dict):
        rec = {"k": req["k"], "t_send": time.monotonic(),
               "prompt": req["prompt"], "max_new": req["max_new"],
               "ok": False, "n": 0}
        try:
            out = self.svc.generate(req["prompt"], req["max_new"])
            rec.update(out)
            rec["ok"] = out["t_first"] is not None
            rec["ttft"] = out["t_first"] - rec["t_send"] if rec["ok"] \
                else float("inf")
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["ttft"] = float("inf")
        rec["t_recv"] = time.monotonic()
        with self._lock:
            self.records.append(rec)

    def _caller(self):
        while not self._closed.is_set():
            self._one(self.deck.draw())

    def run(self, during=None) -> list:
        """Blocks until the window has closed and every request sent has
        answered: those in flight at the close are cut once their first
        token is out. ``during()`` runs in a thread of its own once the
        window is open (the traced run's trace). Returns the records of the
        requests sent in the window."""
        threads = [threading.Thread(target=self._caller, daemon=True)
                   for _ in range(self.mix["callers"])]
        for t in threads:
            t.start()
        time.sleep(float(self.mix.get("ramp_s", 0.0)))
        self.open = self.svc.mark()
        if during is not None:
            threads.append(threading.Thread(target=during, daemon=True))
            threads[-1].start()
        time.sleep(max(0.0, self.open["now"] + self.seconds
                       - time.monotonic()))
        self.close = self.svc.mark()
        self._closed.set()
        self.svc.cut(DRAIN_S)
        deadline = time.monotonic() + DRAIN_S
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        with self._lock:
            return [r for r in self.records
                    if self.open["now"] <= r["t_send"] <= self.close["now"]]


def token_count_gap(window: Window) -> dict:
    """``serve_tok_s`` rests on the program's own counter; this holds the
    counter to what the clients were given. From every reply of the run
    (those of the ramp too): the tokens whose time falls between the two
    readings, a request's first at the rank's ``t_first``, its last at
    ``t_out``, the others evenly between (decode steps are). The counter
    moves a block at a time, so the two differ by a block's tokens at each
    end; the number compared is their gap as a share of the count."""
    import bench_flops
    a, b = window.open["now"], window.close["now"]
    counted = window.close["tokens_generated"] \
        - window.open["tokens_generated"]
    replies = 0.0
    for r in window.records:
        if r["ok"]:
            pre, lo, hi = bench_flops.tokens_in(r, a, b)
            replies += pre + max(0, hi - lo + 1)
    return {"counted": counted, "from_replies": replies,
            "gap": abs(counted - replies) / max(1, counted)}


def draw_sample(sent, seed: int, n: int) -> list:
    """The requests the reference runs over: of those sent in the window
    that ran to their end, the longest, and n - 1 more drawn from the seed."""
    done = sorted((r for r in sent if r["ok"] and not r["cut"]),
                  key=lambda r: r["k"])
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["prompt"]) + r["n"], -r["k"]))
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) + 3).shuffle(rest)
    return [longest] + rest[:max(0, n - 1)]


def deploy(kt, cell: dict, args, env: dict, say):
    """The service through the fabric's own entry; returns it once ready,
    with the rank's pid."""
    cfg, chips = cell["config"], cell["cell"]["chips"]
    spec = {"config": cfg, "seed": args.seed, "chips": chips,
            "rehearse": args.rehearse, "run_dir": R.RUN_DIR}
    svc_file = cfg.get("service", {"file": "benchmark/bench_service.py",
                                   "class": "ServeBench"})
    cls = getattr(R.load_file(os.path.join(R.ROOT, svc_file["file"]), ""),
                  svc_file["class"])
    compute = kt.Compute(tpu=f"v5e-{chips}", env=env, launch_timeout=1100)
    svc = kt.cls(cls, name="bench-" + cell["cell"]["name"].replace(".", "-"),
                 init_kwargs={"spec": spec})
    t = time.monotonic()
    svc.to(compute)
    try:
        rep = svc.report()
    except BaseException:
        svc.teardown()
        raise
    say(f"ready on {rep['platform']} {rep['kind']} x{rep['count']} "
        f"(rank pid {rep['pid']}) {time.monotonic() - R.T_START:.2f}s after "
        f"the start, {time.monotonic() - t:.2f}s after the deploy began; "
        f"init {rep['times']['init_s']:.2f}s, "
        f"warm-up {rep['times']['warmup_s']:.2f}s, "
        f"{rep['compile_events']} programs compiled or fetched, "
        f"bytes in use {rep['bytes_in_use']}")
    return svc, rep["pid"]


def measure(svc, cell: dict, args, seed: int, say, control=False,
            keep_positions=False) -> dict:
    """Ramp, window, then the check in the rank (which frees the engine)."""
    cfg, mix = cell["config"], cell["mix"]
    traced = {}

    def during():
        queries = [q for m in cell["per_layer"]
                   for q in m.get("trace_queries", [])]
        time.sleep(mix.get("trace_after_s", 2.0))
        try:
            traced.update(svc.trace(mix.get("trace_s", 3.0), queries))
        except Exception as e:  # noqa: BLE001 — reported as a failure
            traced["error"] = f"{type(e).__name__}: {e}"

    window = Window(mix, seed, cfg["vocab_size"], args.seconds, svc)
    sent = window.run(during if args.trace and not args.rehearse else None)
    t_open, t_close = window.open["now"], window.close["now"]
    compiles = svc.compiles_between(t_open, t_close)
    failed = sum(not r["ok"] for r in sent)
    say(f"window {t_close - t_open:.2f}s after {t_open - R.T_START:.2f}s of "
        f"set-up: {len(sent)} requests sent in it, {failed} failed, "
        f"{sum(r['ok'] and r['cut'] for r in sent)} cut at the close after "
        f"their first token, {compiles} programs compiled or fetched inside")
    if traced.get("error"):
        raise R.BenchFailure(f"the trace failed: {traced['error']}")
    sample = draw_sample(sent, seed, cell["limits"]["sample_requests"])
    if not sample:
        raise R.BenchFailure("no request sent in the window ran to its end")
    t_pad = -(-bench_traffic.longest_request(mix) // 128) * 128
    fin = svc.finish([{"prompt": r["prompt"], "tokens": r["tokens"],
                       "logprobs": r["logprobs"]} for r in sample],
                     t_pad, list(cell["limits"]["compare"]), control,
                     keep_positions)
    if traced:
        traced["log"] = fin["log"]
    malformed = sum(1 for r in sent if r["ok"] and (
        not (1 <= r["n"] <= r["max_new"]) or (r["n"] < r["max_new"])
        != r["cut"] or len(r["logprobs"]) != r["n"]
        or not all(0 <= t < cfg["vocab_size"] for t in r["tokens"])))
    check = fin["check"]
    say(f"reference over {len(sample)} requests, {check['tokens_compared']} "
        f"served tokens ({100 * check['decided_share']:.0f}% decided), "
        f"{check['reference_s']:.1f}s: {check['numbers']}")
    exact = {"failed_requests": failed, "malformed_replies": malformed,
             "compiles_in_window": compiles,
             "nonfinite": 0 if check["finite"] else 1}
    named = cell["limits"]["compare"]
    gap = token_count_gap(window)
    say(f"tokens in the window: the engine counted {gap['counted']}, the "
        f"replies' own times give {gap['from_replies']:.0f}")
    out = {"window": {"open": window.open, "close": window.close,
                      "setup_s": t_open - R.T_START},
           "records": sent, "traced": traced, "device": fin["device"],
           "attempted": len(sent), "failed": failed, "check": check,
           "compared": {**exact, "token_count_gap": gap["gap"],
                        **{k: check["numbers"][k] for k in named}},
           "limits": {**{k: 0 for k in exact}, "token_count_gap":
                      cell["limits"]["token_count_gap"]["limit"],
                      **{k: v["limit"] for k, v in named.items()}}}
    if control:
        # the control put in the program's place: the same comparison
        out["control_compared"] = {
            **exact, "token_count_gap": gap["gap"],
            **{k: check["control"][k] for k in named}}
    return out


def run(kt, cell: dict, args, env: dict, say) -> dict:
    """Deploy, ramp, window, check, tear down."""
    svc, rank_pid = deploy(kt, cell, args, env, say)
    try:
        return measure(svc, cell, args, args.seed, say)
    finally:
        svc.teardown()
        R.wait_pid_gone(rank_pid)
