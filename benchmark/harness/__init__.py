"""The benchmark's harness: everything that decides a number lives here,
under BENCHMARK.json's `paths`, where a PR that claims a gain cannot
change it. From the program it takes only the system under test (the
training CLIs as child processes, `ServeEngine` in-process) and the
spans, counters and kernel names that system already emits."""
