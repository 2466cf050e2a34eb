"""Mean of the service's verifier_service_queue_depth gauge, sampled
through the window from its --metrics-port."""


def read(run):
    samples = [depth for _, depth, ok in run.observed.get("queue_depth", [])
               if ok]
    return sum(samples) / len(samples) if samples else None
