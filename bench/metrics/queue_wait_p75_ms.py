"""Scheduler: 75th percentile of due time to the tick in which the request
got a slot, over requests due in the window (one still waiting counts at
its age at the close).  Host clock."""
from bench import driver


def read(run):
    return driver.percentile(driver.queue_waits_ms(run.window), 75)
