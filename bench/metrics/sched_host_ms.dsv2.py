"""`sched_host_ms.lm`'s reading, in the DeepSeek-V2 decode pool's cell."""
from bench.harness import load_module, reader_path
read = load_module(reader_path("sched_host_ms.lm")).read
