"""The load generator: a process of its own that imports no JAX and
nothing of the program, so it shares neither the GIL nor the chip with
the server. ``python -m benchmarks.loadgen <plan.json>`` reads a plan
(see ``run.build_plan``), drives ``/v1/completions`` streams against
``127.0.0.1:<port>``, timestamps every chunk with ``time.monotonic()``
(one clock for every process of the machine) and writes one JSON file.

Open loop: every request has a due time fixed beforehand and is sent
then, whatever the server does. Closed loop: ``clients`` tasks, each
sending its next request when the last one ended.
"""

import asyncio
import json
import sys
import time

import aiohttp

now = time.monotonic


class Gen:

    def __init__(self, plan: dict) -> None:
        self.plan = plan
        self.base = f"http://127.0.0.1:{plan['port']}"
        self.records: list[dict] = []
        self.marks: dict = {}
        self.scrapes: dict = {}
        self.gauges: list = []
        self.session: aiohttp.ClientSession = None

    # -- one streamed request -----------------------------------------
    async def stream(self, req: dict, segment: str, due: float) -> dict:
        rec = {"segment": segment, "due": due, "sent": None, "chunks": [],
               "end": None, "ok": False, "want": req["max_tokens"],
               "prompt_tokens": len(req["prompt"]), "error": None}
        self.records.append(rec)
        body = {"model": self.plan["model"], "prompt": req["prompt"],
                "max_tokens": req["max_tokens"], "ignore_eos": True,
                "temperature": 0, "stream": True}
        try:
            rec["sent"] = now()
            async with self.session.post(self.base + "/v1/completions",
                                         json=body) as resp:
                if resp.status != 200:
                    rec["error"] = f"status {resp.status}"
                    return rec
                done = False
                async for raw in resp.content:
                    t = now()
                    if not raw.startswith(b"data: "):
                        continue
                    data = raw[6:].strip()
                    if data == b"[DONE]":
                        done = True
                        break
                    choice = json.loads(data)["choices"][0]
                    n = len(choice["text"].split())
                    if n:
                        rec["chunks"].append([t, n])
                    if choice.get("finish_reason") not in (None, "length"):
                        rec["error"] = f"finish {choice['finish_reason']}"
                rec["end"] = now()
                rec["ok"] = done and rec["error"] is None
        except asyncio.CancelledError:
            rec["cancelled"] = True
            raise
        except Exception as e:  # noqa: BLE001 - a failed request is data
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    # -- the two loops ------------------------------------------------
    async def open_loop(self, t_start: float) -> list:
        tasks = []
        for seg in self.plan["segments"]:
            for req in seg["requests"]:
                due = t_start + req["due"]
                delay = due - now()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.ensure_future(
                    self.stream(req, seg["name"], due)))
        return tasks

    async def closed_client(self, i: int, t_end: float) -> None:
        for rnd in self.plan["rounds"]:
            if now() >= t_end:
                return
            await self.stream(rnd[i], "closed", now())

    # -- shape warm-up (set-up, before the ramp) ----------------------
    async def warm_shapes(self) -> None:
        """Drive every (tokens-in-step, requests-in-step) bucket once.

        The program compiles a small row gather at the first use of each
        pair of buckets, outside its precompiled lattice (PERF.md, open
        questions). Stages of ``decoders`` long streams set the request
        bucket; while they decode, one-token ``probes`` of chosen prompt
        lengths, sent one after the other, set the token bucket."""
        decoders: list = []
        for stage in self.plan.get("warmup", []):
            while len(decoders) < stage["decoders"]:
                req = stage["decoder_requests"][len(decoders)]
                decoders.append(asyncio.ensure_future(
                    self.stream(req, "warmup", now())))
            n = len(decoders)
            while sum(1 for r in self.records[-n:] if r["chunks"]) < n:
                await asyncio.sleep(0.02)
            for req in stage["probes"]:
                body = {"model": self.plan["model"], "prompt": req["prompt"],
                        "max_tokens": 1, "ignore_eos": True,
                        "temperature": 0}
                async with self.session.post(
                        self.base + "/v1/completions", json=body) as r:
                    await r.read()
        for t in decoders:
            t.cancel()
        await asyncio.gather(*decoders, return_exceptions=True)
        if decoders:
            await asyncio.sleep(0.5)   # the server frees the dropped streams
        self.marks["warmed"] = now()

    # -- side channels ------------------------------------------------
    async def get_text(self, path: str) -> str:
        async with self.session.get(self.base + path) as r:
            return await r.text()

    async def post(self, path: str) -> dict:
        async with self.session.post(self.base + path) as r:
            return {"status": r.status, "body": await r.text()}

    async def side(self, t0: float, t1: float) -> None:
        """Scrapes at both ends of the window, gauge samples inside it,
        and the profiler capture in its middle."""
        await asyncio.sleep(max(0.0, t0 - now()))
        self.scrapes["before"] = await self.get_text("/metrics")
        self.marks["scrape_before"] = now()
        trace = self.plan.get("trace")
        every = self.plan.get("gauge_every_s")
        nxt = t0 + (every or 0)
        started = stopped = not trace
        while now() < t1:
            t = now()
            if not started and t >= t0 + trace["start_after_s"]:
                self.marks["profile_start_call"] = now()
                self.marks["profile_start"] = await self.post(
                    "/start_profile")
                self.marks["profile_started"] = now()
                started = True
            elif started and not stopped and \
                    t >= self.marks["profile_started"] + trace["seconds"]:
                self.marks["profile_stop_call"] = now()
                self.marks["profile_stop"] = await self.post("/stop_profile")
                self.marks["profile_stopped"] = now()
                stopped = True
            elif every and t >= nxt:
                self.gauges.append([now(), await self.get_text("/metrics")])
                nxt += every
            await asyncio.sleep(0.05)
        if started and not stopped:
            self.marks["profile_stop"] = await self.post("/stop_profile")
            self.marks["profile_stopped"] = now()
        self.scrapes["after"] = await self.get_text("/metrics")
        self.marks["scrape_after"] = now()

    # -- scoring requests (outside the window) ------------------------
    async def score(self) -> list:
        out = []
        for req in self.plan.get("scoring", []):
            body = {"model": self.plan["model"], "prompt": req["prompt"],
                    "max_tokens": req["max_tokens"], "ignore_eos": True,
                    "temperature": 0, "echo": True, "logprobs": 1}
            async with self.session.post(self.base + "/v1/completions",
                                         json=body) as r:
                text = await r.text()
                if r.status != 200:
                    out.append({"error": f"status {r.status}: {text[:300]}"})
                    continue
                lp = json.loads(text)["choices"][0]["logprobs"]
                out.append({"prompt": req["prompt"],
                            "tokens": [int(t) for t in lp["tokens"]],
                            "token_logprobs": lp["token_logprobs"]})
        return out

    # -- the run ------------------------------------------------------
    async def run(self) -> dict:
        plan = self.plan
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout,
                                         connector=conn) as self.session:
            await self.warm_shapes()
            t_start = now() + 0.2
            if plan["loop"] == "open":
                t0 = t_start + plan["window"]["start"]
                t1 = t0 + plan["window"]["seconds"]
                side = asyncio.ensure_future(self.side(t0, t1))
                tasks = await self.open_loop(t_start)
                await asyncio.sleep(max(0.0, t1 - now()))
                self.marks["in_flight_at_end"] = sum(
                    not t.done() for t in tasks)
                await side
                _, pending = await asyncio.wait(
                    tasks, timeout=plan["drain_cap_s"])
            else:
                t0 = t_start + plan["ramp_s"]
                t1 = t0 + plan["seconds"]
                side = asyncio.ensure_future(self.side(t0, t1))
                tasks = [asyncio.ensure_future(self.closed_client(i, t1))
                         for i in range(plan["clients"])]
                await asyncio.sleep(max(0.0, t1 - now()))
                await side
                # Closed loop: what is in flight at the window's end is
                # cut, not drained; it is in no sample.
                pending = [t for t in tasks if not t.done()]
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            self.marks.update(t_start=t_start, t0=t0, t1=t1,
                              drained=now())
            if plan["loop"] == "closed" and pending:
                # Let the server notice the dropped streams and free
                # their pages before the scoring requests.
                await asyncio.sleep(1.0)
            scoring = await self.score()
            self.marks["scored"] = now()
        return {"marks": self.marks, "records": self.records,
                "scrapes": self.scrapes, "gauges": self.gauges,
                "scoring": scoring}


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    out = asyncio.run(Gen(plan).run())
    with open(plan["out"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
