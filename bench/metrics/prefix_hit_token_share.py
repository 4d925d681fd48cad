"""KV manager: prompt tokens the prefix cache served
(``prefix_tokens_saved``) over prompt tokens admitted in the window, %."""


def read(run):
    admitted = sum(r.prompt_len for r in run.requests
                   if run.in_window(r.req.exec_start))
    if not admitted:
        return None
    return 100.0 * run.prefix_tokens_saved / admitted
