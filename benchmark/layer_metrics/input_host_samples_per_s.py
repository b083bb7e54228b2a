"""layer: input. The benchmark's own span around `TaskDataService.batches`
over the cell's .cbin file, no device, taken in set-up of the traced run."""


def read(run):
    return (run.get("job") or {}).get("input_host_samples_per_s")
