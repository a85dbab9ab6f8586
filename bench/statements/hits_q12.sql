select SearchPhrase, count(*) as c
from hits
where SearchPhrase <> ''
group by SearchPhrase
order by c desc, SearchPhrase
limit 10
